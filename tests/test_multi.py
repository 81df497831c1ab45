"""Multi-design emulation (DESIGN.md §15): isomorphism key, shared-program
retrace behavior, and vmapped-vs-sequential bit-exactness.

The program-sharing contract under test: designs with identical structure
(node kinds, shapes, LUT sizes, Q-formats) but different trained values
share one :func:`repro.rtl.ir.iso_key` and therefore one compiled program
(weights are traced arguments), while ANY structural change — a LUT's kind
or size, an array's shape, an edge format — produces a distinct key and a
separate program. On top of that key, :class:`MultiDesignEmulator` must be
integer-for-integer identical to per-design emulation in every mode.
"""
import copy
import dataclasses
import functools
import importlib.util
import os
import pathlib

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                       # image lacks hypothesis: use shim
    from _hypothesis_compat import given, settings, st

from repro.quant.fixedpoint import FxpFormat
from repro.rtl import (MultiDesignEmulator, ProgramLRU, RTLEmulator,
                       assert_isomorphic, iso_key)
from repro.verify.conformance import run_conformance_batch
from repro.verify.vectors import canonical_graph

ARCHS = ("elastic-lstm", "elastic-conv1d")


@functools.lru_cache(maxsize=None)
def _graph(arch: str, seed: int):
    """Seeded canonical lowering — different seed, different weights, same
    structure (the isomorphic-candidate generator the DSE sweep uses)."""
    return canonical_graph(arch, seed=seed)[0]


def _stimulus(graph, batch=4, seed=0):
    in_edge = graph.edges[graph.inputs[0]]
    rng = np.random.default_rng(seed)
    return rng.integers(in_edge.fmt.lo, in_edge.fmt.hi + 1,
                        (batch,) + tuple(in_edge.shape)).astype(np.int32)


# ---------------------------------------------------------------------------
# the isomorphism key
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 40), st.integers(0, 40))
def test_iso_key_property_weights_do_not_matter(s1, s2):
    """Perturbing ONLY the trained values never changes the key."""
    for arch in ARCHS:
        g1, g2 = _graph(arch, s1), _graph(arch, s2)
        assert iso_key(g1) == iso_key(g2)
        assert g1.iso_key() == iso_key(g1)      # method == module fn
        if s1 != s2:                            # weights genuinely differ...
            arrays = [
                (getattr(a, f.name), getattr(b, f.name))
                for a, b in zip(g1.nodes, g2.nodes)
                for f in dataclasses.fields(a)
                if isinstance(getattr(a, f.name), np.ndarray)
            ]
            assert any(not np.array_equal(x, y) for x, y in arrays)


def _mutate(graph, what: str):
    g = copy.deepcopy(graph)
    if what == "lut_kind":
        n = next(n for n in g.nodes if n.op == "act_lut")
        n.kind = ("hard_tanh" if n.kind == "hard_sigmoid"
                  else "hard_sigmoid")
    elif what == "lut_size":
        n = next(n for n in g.nodes if n.op == "act_lut")
        n.in_fmt = FxpFormat(n.in_fmt.total_bits + 1, n.in_fmt.frac_bits)
    elif what == "weight_shape":
        for n in g.nodes:
            for f in dataclasses.fields(n):
                v = getattr(n, f.name)
                if isinstance(v, np.ndarray):
                    setattr(n, f.name, np.concatenate([v, v], axis=0))
                    return g
        raise AssertionError("no array field found to mutate")
    elif what == "edge_fmt":
        name = sorted(g.edges)[0]
        e = g.edges[name]
        g.edges[name] = dataclasses.replace(
            e, fmt=FxpFormat(e.fmt.total_bits + 2, e.fmt.frac_bits))
    return g


@pytest.mark.parametrize("what",
                         ["lut_kind", "lut_size", "weight_shape", "edge_fmt"])
@pytest.mark.parametrize("arch", ARCHS)
def test_iso_key_distinct_on_structural_change(arch, what):
    base = _graph(arch, 0)
    assert iso_key(_mutate(base, what)) != iso_key(base)


# ---------------------------------------------------------------------------
# one retrace across isomorphic designs (the tentpole's economic claim)
# ---------------------------------------------------------------------------


def test_isomorphic_designs_share_one_program():
    lru = ProgramLRU(4)
    ems = [RTLEmulator(_graph("elastic-lstm", s), mode="jnp", programs=lru)
           for s in (0, 1, 2)]
    x = _stimulus(ems[0].graph)
    outs = [np.asarray(em.run_int(x).outputs, np.int64) for em in ems]

    # one trace TOTAL: designs #1 and #2 reuse #0's compiled program
    assert sum(em.trace_count for em in ems) == 1
    stats = lru.stats()
    assert stats["misses"] == 1 and stats["hits"] == 2
    # has_program probes the shared LRU without building
    assert ems[2].has_program(x.shape, x.dtype)
    # the shared program is weight-GENERIC, not weight-frozen: different
    # traced params through the same program give different outputs
    assert not np.array_equal(outs[0], outs[1])


def test_distinct_structures_do_not_share_a_program():
    lru = ProgramLRU(4)
    a = RTLEmulator(_graph("elastic-lstm", 0), mode="jnp", programs=lru)
    b = RTLEmulator(_graph("elastic-conv1d", 0), mode="jnp", programs=lru)
    a.run_int(_stimulus(a.graph))
    b.run_int(_stimulus(b.graph))
    assert a.trace_count == 1 and b.trace_count == 1
    assert lru.stats()["misses"] == 2


# ---------------------------------------------------------------------------
# vmapped vs sequential bit-exactness — all 3 modes, both shipped archs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_vmapped_bit_exact_vs_every_sequential_mode(arch):
    graphs = [_graph(arch, s) for s in (0, 1)]
    x = _stimulus(graphs[0])
    multi = MultiDesignEmulator(graphs)
    out = np.asarray(multi.run_int(x).outputs, np.int64)
    assert out.shape[0] == multi.k
    assert multi.trace_count == 1

    for mode in ("jnp", "fused", "pallas"):
        for k, g in enumerate(graphs):
            ref = np.asarray(RTLEmulator(g, mode=mode).run_int(x).outputs,
                             np.int64)
            assert np.array_equal(out[k], ref), (arch, mode, k)

    # the built-in sequential cross-check path agrees too
    assert np.array_equal(out, multi.run_int_sequential(x))


def test_per_design_stimulus_routes_row_k_to_design_k():
    graphs = [_graph("elastic-lstm", s) for s in (0, 1, 2)]
    xs = np.stack([_stimulus(graphs[0], seed=s) for s in range(3)])
    multi = MultiDesignEmulator(graphs)
    out = np.asarray(multi.run_int(xs, per_design=True).outputs, np.int64)
    for k, g in enumerate(graphs):
        ref = np.asarray(multi.emulators[k].run_int(xs[k]).outputs, np.int64)
        assert np.array_equal(out[k], ref), k
    with pytest.raises(ValueError, match="design axis"):
        multi.run_int(xs[:2], per_design=True)


def test_assert_isomorphic_names_the_offender():
    graphs = [_graph("elastic-lstm", 0), _graph("elastic-conv1d", 0)]
    with pytest.raises(ValueError, match="not program-isomorphic"):
        assert_isomorphic(graphs)
    with pytest.raises(ValueError, match="at least one graph"):
        MultiDesignEmulator([])


def test_run_conformance_batch_cross_checks_every_design():
    reports = run_conformance_batch([_graph("elastic-lstm", s)
                                     for s in (0, 1)])
    assert len(reports) == 2
    for rep in reports:
        assert rep.passed
        assert rep.modes[0] == "vmap-jnp"
        assert rep.modes_bit_exact and rep.oracle_within_budget
        vs = {k: v for k, v in rep.mode_max_diff.items()
              if k.startswith("vmap-jnp-vs-")}
        assert vs and all(v == 0 for v in vs.values())


def test_sharded_design_axis_on_four_devices():
    """shard=True on 4 forced host devices: the design axis splits over a
    mesh of 4 distinct devices and stays integer-equal to the unsharded
    vmap; a K the devices cannot split raises instead of falling back
    (subprocess, so this process keeps seeing 1 device)."""
    import subprocess
    import sys
    import textwrap

    root = str(pathlib.Path(__file__).resolve().parents[1])
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {root + "/src"!r})
        import numpy as np
        from repro.rtl import MultiDesignEmulator
        from repro.verify.vectors import canonical_graph, generate_vectors

        graphs = [canonical_graph("elastic-lstm", seed=s)[0]
                  for s in range(8)]
        stim = generate_vectors(graphs[0]).stimulus
        sharded = MultiDesignEmulator(graphs, shard=True)
        devs = list(sharded.mesh.devices.flat)
        assert len({{d.id for d in devs}}) == 4, devs
        a = np.asarray(sharded.run_int(stim).outputs)
        b = np.asarray(MultiDesignEmulator(graphs).run_int(stim).outputs)
        assert np.array_equal(a, b)
        try:
            MultiDesignEmulator(graphs[:3], shard=True)
        except ValueError as e:
            assert "divide" in str(e), e
        else:
            raise AssertionError("K=3 on 4 devices did not raise")
        print("sharded-dse-ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "sharded-dse-ok" in r.stdout


def test_smoke_mesh_refuses_missing_devices():
    import jax

    from repro.launch.mesh import make_smoke_mesh

    with pytest.raises(RuntimeError, match="needs"):
        make_smoke_mesh(shape=(len(jax.devices()) + 1, 1))


# ---------------------------------------------------------------------------
# satellite: experiments/hillclimb.py must not mutate XLA_FLAGS at import
# ---------------------------------------------------------------------------


def _load_hillclimb():
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "experiments" / "hillclimb.py")
    spec = importlib.util.spec_from_file_location("_hillclimb_under_test",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_hillclimb_import_leaves_environment_alone():
    before = os.environ.get("XLA_FLAGS")
    _load_hillclimb()
    assert os.environ.get("XLA_FLAGS") == before


def test_apply_xla_flags_guarded_and_idempotent():
    mod = _load_hillclimb()
    env = {}
    first = mod.apply_xla_flags(env)
    assert "--xla_force_host_platform_device_count=512" in first
    assert mod.apply_xla_flags(env) == first            # second call: no-op
    # a user-chosen value for the same flag NAME is never overridden
    user = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    mod.apply_xla_flags(user)
    assert "device_count=512" not in user["XLA_FLAGS"]
    assert user["XLA_FLAGS"].startswith(
        "--xla_force_host_platform_device_count=4")
    assert "concurrency_optimized_scheduler" in user["XLA_FLAGS"]
