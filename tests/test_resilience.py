"""Fault injection + fault-tolerant deployment (repro.resilience).

Covers the DESIGN.md §12 contracts: the seeded SEU/chaos harness over the
emulator's prepared memories, the guarded-deployment state machine
(retry/timeout/breaker/canary/fallback), and the scripted chaos scenario
that is the ISSUE-7 acceptance bar — all with injected clocks and numpy
generators, run-twice-identical.
"""
from pathlib import Path

import numpy as np
import pytest

from repro.core.target import Deployment
from repro.obs import MetricsRegistry
from repro.resilience import (CLOSED, HALF_OPEN, OPEN, ChaosSpec,
                              CircuitBreaker, FallbackPolicy, FaultPlan,
                              FaultSpec, FaultyDeployment, GuardedDeployment,
                              GuardExhausted, GuardPolicy, TransientFault,
                              VirtualClock, run_chaos)
from repro.verify import canary_check, canonical_graph, generate_vectors

PLAN_PATH = str(Path(__file__).resolve().parents[1] / "examples"
                / "chaos_plan.json")


@pytest.fixture(scope="module")
def lstm_graph():
    graph, _, _ = canonical_graph("elastic-lstm")
    return graph


@pytest.fixture(scope="module")
def lstm_vectors(lstm_graph):
    return generate_vectors(lstm_graph)


def _rtl_dep(graph):
    from repro.energy.hw import get_hw
    from repro.rtl.backend import RTLExecutable

    return RTLExecutable(graph=graph, artifacts={}, hw=get_hw("xc7s15"))


def _xla_fallback(graph):
    import jax

    from repro.core.target import XLADeployment
    from repro.energy.hw import XC7S15
    from repro.rtl.emulator import reference_apply

    return XLADeployment(fn=jax.jit(lambda x: reference_apply(graph, x)),
                         hw=XC7S15)


# --------------------------------------------------------------------------- #
# FaultSpec / FaultPlan
# --------------------------------------------------------------------------- #


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(kind="gamma_ray", at_call=0)
    with pytest.raises(ValueError, match="never fires"):
        FaultSpec(kind="transient")              # no trigger at all
    with pytest.raises(ValueError, match="probability"):
        FaultSpec(kind="transient", probability=1.5)
    with pytest.raises(ValueError, match="bit"):
        FaultSpec(kind="bitflip", at_call=0, bit=32)
    with pytest.raises(ValueError, match="delay_s"):
        FaultSpec(kind="latency", at_call=0, delay_s=-1.0)


def test_fault_plan_json_round_trip(tmp_path):
    plan = FaultPlan(seed=2024, faults=(
        FaultSpec(kind="transient", at_call=2),
        FaultSpec(kind="bitflip", at_call=9, memory="lstm_cell_l0.w",
                  word=3, bit=31),
        FaultSpec(kind="latency", probability=0.25, once=False,
                  delay_s=0.5)))
    back = FaultPlan.from_json(plan.to_json())
    assert back == plan
    p = tmp_path / "plan.json"
    plan.save(str(p))
    assert FaultPlan.load(str(p)) == plan
    # the checked-in CI scenario must stay loadable
    shipped = FaultPlan.load(PLAN_PATH)
    assert {f.kind for f in shipped.faults} == {"transient", "latency",
                                                "bitflip"}


def test_virtual_clock():
    clk = VirtualClock(start=1.0)
    assert clk() == clk.now() == 1.0
    clk.sleep(0.5)
    clk.advance(0.25)
    clk.sleep(-3.0)                              # never goes backwards
    assert clk.now() == 1.75


# --------------------------------------------------------------------------- #
# SEU model: emulator memories + flip_bit
# --------------------------------------------------------------------------- #


def test_emulator_memories_and_flip_bit(lstm_graph, lstm_vectors):
    dep = _rtl_dep(lstm_graph)
    emu = dep.emulator
    mems = emu.memories()
    assert ("lstm_cell_l0", "w") in mems and \
        ("hard_sigmoid_lut", "table") in mems
    before = np.asarray(emu.prepared("lstm_cell_l0")["w"]).reshape(-1)
    new = emu.flip_bit("lstm_cell_l0", "w", 0, 7)
    assert new == int(before[0]) ^ (1 << 7)
    assert emu.seu_flips == 1
    # silent: no exception, but the canary catches it on the rail rows
    assert not canary_check(dep, lstm_vectors, n=4).passed
    # XOR is an involution: re-flipping restores bit-exact behavior
    emu.flip_bit("lstm_cell_l0", "w", 0, 7)
    assert canary_check(dep, lstm_vectors, n=4).passed


def test_flip_bit_sign_bit_and_word_wrap(lstm_graph):
    emu = _rtl_dep(lstm_graph).emulator
    flat = np.asarray(emu.prepared("linear_head")["w"], np.int32).reshape(-1)
    # bit 31 (the int32 sign bit) must not overflow int32 arithmetic —
    # the emulator XORs through a uint32 view; mirror that here
    u = flat.copy().view(np.uint32)
    u[0] ^= np.uint32(1) << np.uint32(31)
    expected = int(u.view(np.int32)[0])
    assert emu.flip_bit("linear_head", "w", 0, 31) == expected
    # word index wraps modulo the flat size (a plan can't miss the array);
    # XOR involution: the wrapped flip lands on word 0 and restores it
    assert emu.flip_bit("linear_head", "w", flat.size, 31) == int(flat[0])
    with pytest.raises(KeyError):
        emu.flip_bit("linear_head", "nope", 0, 0)
    with pytest.raises(ValueError):
        emu.flip_bit("linear_head", "w", 0, 32)


def test_flip_bit_invalidates_compiled_programs(lstm_graph, lstm_vectors):
    """The jitted programs close over the prepared constants, so an SEU
    only becomes visible through program invalidation — a flip after a
    dispatch must still corrupt the next dispatch."""
    dep = _rtl_dep(lstm_graph)
    stim = lstm_vectors.stimulus
    first = np.asarray(dep.emulator.run_int(stim).outputs)
    assert dep.emulator.cache_stats()["misses"] == 1
    dep.emulator.flip_bit("lstm_cell_l0", "w", 0, 7)
    second = np.asarray(dep.emulator.run_int(stim).outputs)
    assert not np.array_equal(first, second)
    assert dep.emulator.cache_stats()["misses"] == 2   # re-traced


# --------------------------------------------------------------------------- #
# FaultyDeployment
# --------------------------------------------------------------------------- #


class _EchoDeployment(Deployment):
    target = "echo"

    def __init__(self):
        self.calls = 0

    def __call__(self, x):
        self.calls += 1
        import jax.numpy as jnp

        return jnp.asarray(x)


def test_faulty_transient_and_once(lstm_vectors):
    inner = _EchoDeployment()
    plan = FaultPlan(faults=(FaultSpec(kind="transient", at_call=1),))
    fd = FaultyDeployment(inner, plan)
    x = np.ones((1, 2), np.float32)
    fd(x)
    with pytest.raises(TransientFault):
        fd(x)
    fd(x)                                        # once=True: disarmed
    assert [f["kind"] for f in fd.injected] == ["transient"]


def test_faulty_stuck_output_and_latency():
    inner = _EchoDeployment()
    clk = VirtualClock()
    mx = MetricsRegistry()
    plan = FaultPlan(faults=(
        FaultSpec(kind="stuck_output", at_call=0, value=3.0),
        FaultSpec(kind="latency", at_call=1, delay_s=0.75)))
    fd = FaultyDeployment(inner, plan, clock=clk, metrics=mx)
    out = fd(np.zeros((2, 2), np.float32))
    assert np.all(np.asarray(out) == 3.0)        # wedged output register
    fd(np.zeros((2, 2), np.float32))
    assert clk.now() == 0.75                     # stall on the virtual clock
    assert mx.counter("resilience.faults_injected").value == 2
    assert mx.counter("resilience.faults_injected.latency").value == 1


def test_faulty_bitflip_needs_rtl():
    plan = FaultPlan(faults=(FaultSpec(kind="bitflip", at_call=0),))
    fd = FaultyDeployment(_EchoDeployment(), plan)
    with pytest.raises(ValueError, match="no RTL emulator"):
        fd(np.zeros((1, 1), np.float32))


def test_faulty_bitflip_unknown_memory(lstm_graph):
    plan = FaultPlan(faults=(FaultSpec(kind="bitflip", at_call=0,
                                       memory="nope.w"),))
    fd = FaultyDeployment(_rtl_dep(lstm_graph), plan)
    with pytest.raises(ValueError, match="addressable memories"):
        fd(np.zeros((1, 2), np.float32))


def test_faulty_probabilistic_schedule_is_seeded():
    spec = FaultSpec(kind="transient", probability=0.3, once=False)

    def fire_pattern():
        fd = FaultyDeployment(_EchoDeployment(),
                              FaultPlan(faults=(spec,), seed=11))
        fired = []
        for _ in range(32):
            try:
                fd(np.zeros((1, 1), np.float32))
                fired.append(0)
            except TransientFault:
                fired.append(1)
        return fired

    a, b = fire_pattern(), fire_pattern()
    assert a == b and 0 < sum(a) < 32            # deterministic, non-trivial


# --------------------------------------------------------------------------- #
# CircuitBreaker
# --------------------------------------------------------------------------- #


def test_breaker_state_machine():
    clk = VirtualClock()
    mx = MetricsRegistry()
    pol = GuardPolicy(breaker_threshold=2, breaker_cooldown_s=1.0)
    b = CircuitBreaker(pol, clock=clk, metrics=mx)
    assert b.state == CLOSED and b.allow()
    b.record_failure()
    assert b.state == CLOSED                     # under threshold
    b.record_failure()
    assert b.state == OPEN and b.trips == 1
    assert not b.allow()                         # cooling down
    clk.advance(1.0)
    assert b.allow() and b.state == HALF_OPEN    # probe admitted
    b.record_failure()
    assert b.state == OPEN and b.trips == 2      # failed probe re-opens
    clk.advance(1.0)
    assert b.allow()
    b.record_success()
    assert b.state == CLOSED and b.failures == 0
    assert mx.counter("resilience.breaker.open").value == 2
    assert mx.counter("resilience.breaker.closed").value == 1


def test_breaker_quarantine_never_half_opens():
    clk = VirtualClock()
    b = CircuitBreaker(GuardPolicy(breaker_cooldown_s=0.1), clock=clk)
    b.trip(quarantine=True)
    clk.advance(100.0)
    assert not b.allow() and b.quarantined       # corrupted HW can't heal
    b.reset()                                    # operator reflash
    assert b.state == CLOSED and b.allow() and not b.quarantined


# --------------------------------------------------------------------------- #
# GuardedDeployment
# --------------------------------------------------------------------------- #


class _FlakyDeployment(Deployment):
    """Fails the first ``n_fail`` calls, then succeeds."""

    target = "flaky"

    def __init__(self, n_fail):
        self.n_fail = n_fail
        self.calls = 0

    def __call__(self, x):
        self.calls += 1
        if self.calls <= self.n_fail:
            raise RuntimeError("flaked")
        import jax.numpy as jnp

        return jnp.asarray(x) + 1


def test_guard_retry_heals_transient():
    clk = VirtualClock()
    mx = MetricsRegistry()
    g = GuardedDeployment(_FlakyDeployment(2),
                          policy=GuardPolicy(max_retries=2,
                                             breaker_threshold=5),
                          clock=clk, rng=np.random.default_rng(0),
                          metrics=mx)
    res = g.call(np.zeros((1,), np.float32))
    assert res.retries == 2 and res.source == "primary"
    assert not res.degraded
    assert mx.counter("resilience.retries").value == 2
    assert g.breaker.state == CLOSED             # success reset the count
    # backoff slept on the injected clock: base*(1±j) + base*mult*(1±j)
    pol = g.policy
    lo = (pol.backoff_base_s * (1 - pol.jitter_frac)
          * (1 + pol.backoff_mult))
    hi = (pol.backoff_base_s * (1 + pol.jitter_frac)
          * (1 + pol.backoff_mult))
    assert lo <= clk.now() <= hi


def test_guard_backoff_jitter_is_deterministic():
    def elapsed():
        clk = VirtualClock()
        g = GuardedDeployment(_FlakyDeployment(2),
                              policy=GuardPolicy(max_retries=2,
                                                 breaker_threshold=5),
                              clock=clk, rng=np.random.default_rng(42),
                              metrics=MetricsRegistry())
        g.call(np.zeros((1,), np.float32))
        return clk.now()

    assert elapsed() == elapsed()                # same rng -> same jitter


def test_guard_timeout_counts_as_failure(lstm_graph, lstm_vectors):
    """A latency fault longer than timeout_s fails the attempt even though
    the call returns — the retry (clean: once=True disarmed it) serves."""
    clk = VirtualClock()
    mx = MetricsRegistry()
    plan = FaultPlan(faults=(FaultSpec(kind="latency", at_call=0,
                                       delay_s=1.0),))
    faulty = FaultyDeployment(_rtl_dep(lstm_graph), plan, clock=clk,
                              metrics=mx)
    g = GuardedDeployment(faulty,
                          policy=GuardPolicy(timeout_s=0.5, max_retries=1,
                                             breaker_threshold=5),
                          clock=clk, rng=np.random.default_rng(0),
                          metrics=mx)
    res = g.call(lstm_vectors.stimulus_f()[:1])
    assert res.retries == 1 and res.source == "primary"
    assert mx.counter("resilience.timeouts").value == 1


def test_guard_canary_detects_seu_and_quarantines(lstm_graph, lstm_vectors):
    clk = VirtualClock()
    mx = MetricsRegistry()
    dep = _rtl_dep(lstm_graph)
    g = GuardedDeployment(dep, policy=GuardPolicy(canary_every=2),
                          canary=lstm_vectors, clock=clk,
                          rng=np.random.default_rng(0), metrics=mx)
    x = lstm_vectors.stimulus_f()[:1]
    assert g.call(x).canary_passed is True       # healthy probe at call 0
    dep.emulator.flip_bit("lstm_cell_l0", "w", 0, 7)
    g.call(x)                                    # call 1: no probe due
    with pytest.raises(GuardExhausted):          # call 2: probe detects
        g.call(x)
    assert g.breaker.quarantined
    assert len(g.detections) == 1
    assert mx.counter("resilience.faults_detected").value == 1
    assert mx.counter("resilience.requests_lost").value == 1
    assert not g.can_serve()                     # no fallback -> drained


def test_guard_fallback_chain_order():
    clk = VirtualClock()
    mx = MetricsRegistry()

    def bad(x):
        raise RuntimeError("alternate down too")

    calls = []

    def good(x):
        calls.append(x)
        return "served"

    g = GuardedDeployment(
        _FlakyDeployment(10),                    # primary never succeeds
        policy=GuardPolicy(max_retries=0, breaker_threshold=1),
        fallback=FallbackPolicy(alternates=(("first", bad),
                                            ("second", good))),
        clock=clk, rng=np.random.default_rng(0), metrics=mx)
    res = g.call("x")
    assert res.source == "second" and res.degraded and res.value == "served"
    assert mx.counter("resilience.fallback_errors").value == 1
    assert mx.counter("resilience.fallbacks").value == 1
    assert g.can_serve()                         # fallback keeps it serving


def test_guard_call_dunder_returns_value():
    g = GuardedDeployment(_FlakyDeployment(0),
                          policy=GuardPolicy(breaker_threshold=5),
                          clock=VirtualClock(),
                          rng=np.random.default_rng(0),
                          metrics=MetricsRegistry())
    out = g(np.zeros((2,), np.float32))
    assert np.all(np.asarray(out) == 1.0)        # Deployment contract


def test_deployment_guarded_hook(lstm_graph, lstm_vectors):
    """Deployment.guarded() wraps any registry-produced artifact."""
    dep = _rtl_dep(lstm_graph)
    g = dep.guarded(canary=lstm_vectors, clock=VirtualClock(),
                    rng=np.random.default_rng(0), metrics=MetricsRegistry())
    assert isinstance(g, GuardedDeployment)
    assert g.target == "rtl" and g.graph is lstm_graph
    assert g.probe() is True


# --------------------------------------------------------------------------- #
# Canary slice API
# --------------------------------------------------------------------------- #


def test_vectorset_head_slice(lstm_vectors):
    h = lstm_vectors.head(4)
    assert h.n_vectors == 4
    assert np.array_equal(h.stimulus, lstm_vectors.stimulus[:4])
    assert np.array_equal(h.response, lstm_vectors.response[:4])
    assert h.meta["slice"] == "head(4)"
    assert lstm_vectors.head(10_000).n_vectors == lstm_vectors.n_vectors
    with pytest.raises(ValueError):
        lstm_vectors.head(0)


def test_vectorset_window_wraps(lstm_vectors):
    n = lstm_vectors.n_vectors
    w = lstm_vectors.window(n - 2, 4)
    rows = [n - 2, n - 1, 0, 1]
    assert np.array_equal(w.stimulus, lstm_vectors.stimulus[rows])
    assert np.array_equal(w.response, lstm_vectors.response[rows])
    assert w.meta["slice"] == f"window({n - 2}, 4)"
    with pytest.raises(ValueError):
        lstm_vectors.window(0, 0)


def test_canary_probes_sweep_the_golden_set(lstm_graph, lstm_vectors):
    """Successive probes replay successive slices, so a sweep covers every
    golden row: a fault the sweep misses is one the full set misses."""
    n = lstm_vectors.n_vectors
    guard = GuardedDeployment(_rtl_dep(lstm_graph),
                              policy=GuardPolicy(canary_slice=4),
                              canary=lstm_vectors,
                              metrics=MetricsRegistry())
    starts = []
    for _ in range(n // 4):
        starts.append(guard.canary_row)
        assert guard.probe()
    assert starts == list(range(0, n, 4)) and guard.canary_row == 0
    guard.primary.emulator.flip_bit("lstm_cell_l0", "w", 0, 7)
    full = canary_check(guard.primary, lstm_vectors, n=n).passed
    sweep = [guard.probe() for _ in range(n // 4)]
    assert all(sweep) == full


def test_canary_check_float_path(lstm_graph, lstm_vectors):
    """Host-executed deployments answer in float; the canary re-encodes at
    the output format and still demands integer-exact codes."""
    fb = _xla_fallback(lstm_graph)
    res = canary_check(fb, lstm_vectors, n=4)
    assert res.passed and res.path == "float"


# --------------------------------------------------------------------------- #
# The acceptance scenario (ISSUE 7) + determinism audit
# --------------------------------------------------------------------------- #


def _acceptance_spec():
    return ChaosSpec(
        plan=FaultPlan.load(PLAN_PATH),
        n_requests=24, seed=7,
        policy=GuardPolicy(timeout_s=0.25, max_retries=2,
                           breaker_threshold=3, canary_every=4))


def test_chaos_scenario_elastic_lstm(lstm_graph):
    """Injected BRAM bit-flip -> canary detection within one probe
    interval -> breaker quarantine -> RTL→XLA failover with zero
    post-detection corrupted responses, all recorded in the report and the
    resilience.* counters."""
    dep = _rtl_dep(lstm_graph)
    rep = run_chaos(dep, _acceptance_spec(),
                    fallback=FallbackPolicy.to_xla(_xla_fallback(lstm_graph)))
    assert rep.passed and rep.detected and rep.recovered
    assert rep.corrupted_after_detection == 0
    assert rep.requests_lost == 0                # the workload kept serving
    assert 0 <= rep.mttr_requests <= 4           # within one probe interval
    assert rep.final_breaker_state == OPEN and rep.breaker_trips == 1
    assert rep.counters["resilience.faults_injected"] == 3
    assert rep.counters["resilience.faults_detected"] == 1
    assert rep.counters["resilience.fallbacks"] > 0
    assert rep.counters["resilience.retries"] > 0
    kinds = [f["kind"] for f in rep.faults_injected]
    assert kinds == ["transient", "latency", "bitflip"]
    # post-detection requests all served degraded by the XLA alternate
    det = rep.faults_detected[0]["request"]
    post = [r for r in rep.requests if r["request"] > det]
    assert post and all(r["source"] == "xla" and r["correct"]
                        for r in post)


def test_chaos_run_twice_identical(lstm_graph):
    """Determinism audit: every retry/jitter/fault path draws from injected
    generators and the shared VirtualClock, so the full report JSON is
    byte-identical across runs (the emit-twice golden-artifact pattern)."""
    fb = FallbackPolicy.to_xla(_xla_fallback(lstm_graph))
    r1 = run_chaos(_rtl_dep(lstm_graph), _acceptance_spec(), fallback=fb)
    r2 = run_chaos(_rtl_dep(lstm_graph), _acceptance_spec(), fallback=fb)
    assert r1.to_json() == r2.to_json()


def test_chaos_needs_graph_or_vectors():
    with pytest.raises(ValueError, match="vectors"):
        run_chaos(_EchoDeployment(),
                  ChaosSpec(plan=FaultPlan(
                      faults=(FaultSpec(kind="transient", at_call=0),))))
