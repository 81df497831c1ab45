"""The reduction of the program's own spans (``repro.obs`` in profiler
mode), on a synthesised trace (``fixtures/xspace_program.pbtxt``) with
hand-computed stages, and through ``bench/call_split.py`` on a CPU trace."""
from __future__ import annotations

import pathlib
import warnings

import pytest

from bench import counts
from bench.harness import program_spans, tracing

FIXTURES = pathlib.Path(__file__).with_name("fixtures")


def _profile(name):
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return ProfileData.from_text_proto((FIXTURES / name).read_text())


@pytest.fixture(scope="module")
def split():
    return program_spans.reduce_profile(_profile("xspace_program.pbtxt"))


def test_stage_medians_clip_to_the_window(split):
    assert split.median_s == {
        # calls of 6000, 4000 and 2000 ns (the last clipped at the end)
        "rtl.call": pytest.approx(4e-6),
        "rtl.emulator.quantize": pytest.approx(0.5e-6),
        # 2000, 500 and 1300 ns in the window
        "rtl.emulator.dispatch": pytest.approx(1.3e-6),
        # the third unpack lies past the window: 1000 and 500 ns
        "rtl.emulator.unpack": pytest.approx(0.75e-6),
        "rtl.lower": pytest.approx(1e-6),
        "rtl.multi.dispatch": pytest.approx(3e-6)}


def test_call_self_time_is_less_the_union_on_its_own_line(split):
    # self times 2000 (rtl.lower inside dispatch counted once, where a sum
    # would give 1000; the worker thread's span not at all, where it would
    # give 1400), 2500, and 200 in the window
    assert split.call_self_s == pytest.approx(2e-6)


def test_programs_per_call_counts_what_starts_in_the_window(split):
    assert (split.calls, split.modules) == (3, 7)
    assert split.programs_per_call == pytest.approx(7 / 3)


def test_program_spans_leave_the_benchmark_reduction_as_it_was():
    s = tracing.reduce_profile(_profile("xspace_program.pbtxt"),
                               counts.KERNELS)
    assert s.window_s == pytest.approx(20e-6)
    assert s.busy_s == pytest.approx(2.5e-6)
    assert s.idle_gaps == [("bench.fetch", pytest.approx(12.5e-6)),
                           ("bench.call", pytest.approx(5e-6))]
    assert s.kernel_calls == {"lstm_window_int": 2}


def test_nothing_to_read_without_program_spans():
    split = program_spans.reduce_profile(_profile("xspace.pbtxt"))
    assert split.median_s == {}
    assert split.call_self_s is None
    assert (split.calls, split.modules) == (0, 1)
    assert split.programs_per_call is None
    assert program_spans.Split().programs_per_call is None


def test_call_split_of_lstm_b1_on_the_cpu(small):
    """``bench/call_split.py``'s windows at test size, on the CPU: the
    stages of the call are read from the windows with the program's spans
    only, and the answers stay correct."""
    from bench import call_split
    from bench.harness import core, spec

    cell = spec.load_cell("lstm-b1")
    run = core.Run(cell=cell, seed=2 ** 31 + 7, seconds=0.3, trace=True,
                   traffic_overrides=small(cell))
    state = cell.kind.setup(run)
    rows = [call_split.traced_window(run, state, program=p)
            for p in (False, True)]
    assert "rtl.call_ms" not in rows[0]
    out = call_split.summarize(rows)
    for k in call_split.STAGES:
        assert out[f"{k}_ms"] > 0
    assert out["call_self_ms"] > 0
    assert out["rtl.call_ms"] <= out["issue_ms_spans"]  # nested in it
    assert out["stages_ms"] > 0
    assert out["mismatched_codes"] == 0
    assert out["programs_per_call"] is None      # no device plane here
