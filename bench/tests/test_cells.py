"""The benchmark's data resolves, its generator is seeded, and
``bench/run.py`` refuses a CPU."""
from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

from bench.harness import loadgen, spec  # noqa: E402

DOC = spec.benchmark()
CELLS = [w["name"] for w in DOC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_file_by_name(name):
    cell = spec.load_cell(name)
    for fn in ("setup", "window", "payload", "check_outputs"):
        assert callable(getattr(cell.kind, fn))
    for fn in ("make_params", "forward"):
        assert callable(getattr(cell.ref, fn))
    assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported, (m["name"], m["moves"])


def test_every_config_is_used_and_every_metric_file_exists():
    used = {w["config"] for w in DOC["workloads"]}
    assert used == {c["name"] for c in DOC["configs"]}
    for m in DOC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("no-such-cell")


def test_windows_are_a_function_of_the_seed():
    a = loadgen.windows(np.random.default_rng(5), 8, (6, 1), 1.0)
    b = loadgen.windows(np.random.default_rng(5), 8, (6, 1), 1.0)
    assert a.dtype == np.float32 and a.shape == (8, 6, 1)
    assert np.array_equal(a, b)


def _run_cli(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lstm-bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_refuses_a_cpu_and_prints_no_result():
    res = _run_cli(ROOT, _cpu_env())
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "needs a TPU" in res.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_cli(tmp_path, _cpu_env())
    assert res.returncode != 0
    assert res.stdout.strip() == ""
