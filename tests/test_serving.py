"""Fleet-scale serving: queue, micro-batcher, router, farm, loadgen.

The load-bearing claims (ISSUE 9 acceptance):

* micro-batched results are BIT-EXACT vs per-request execution on the RTL
  target (batch rows are independent in every template);
* the admission queue sheds at capacity and expires on deadline — nothing
  admitted is ever silently dropped;
* affinity routing converges: once steady mixed traffic has compiled its
  shapes, ``RTLEmulator.trace_count`` stops growing;
* the seeded loadgen replays identically (run-twice-identical stats JSON
  under an injected VirtualClock);
* ``Deployment.measure`` percentiles exclude warmup runs.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.obs import MetricsRegistry
from repro.resilience.faults import VirtualClock
from repro.serving import (DONE, EXPIRED, SHED, AcceleratorFarm,
                           AdmissionQueue, AffinityRouter, DesignPool,
                           FarmConfig, MicroBatcher, NoServeableMember,
                           ServeRequest, bucket_for, pack, pad_window,
                           padded_batch_size)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# --------------------------------------------------------------------------- #
# shared fixtures / fakes
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def lstm_exe():
    """The paper's LSTM reference design, translated once per module."""
    import jax

    from repro.configs.elastic_lstm import config
    from repro.model.layers import init_params
    from repro.model.lstm import lstm_schema
    from repro.rtl.backend import translate_rtl

    cfg = config()
    params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
    _, exe = translate_rtl(cfg, params)
    return exe


class _Member:
    """Duck-typed farm member: callable on (B, L, F), optional health gate
    and program-cache set for affinity, optional failure injection."""

    def __init__(self, healthy=True, fail=False):
        self.healthy = healthy
        self.fail = fail
        self.calls = 0
        self._held = set()

    def can_serve(self):
        return self.healthy

    def holds_program(self, shape, dtype):
        return (tuple(shape), np.dtype(dtype).name) in self._held

    def __call__(self, arr):
        if self.fail:
            raise RuntimeError("member down")
        self.calls += 1
        arr = np.asarray(arr)
        self._held.add((arr.shape, np.dtype(arr.dtype).name))
        return arr.sum(axis=(1, 2))[:, None]


def _fake_farm(members, *, lengths=(8,), clock=None, **cfg_kw):
    clock = clock if clock is not None else VirtualClock()
    pool = DesignPool(family="fake", members={ln: list(members)
                                              for ln in lengths})
    farm = AcceleratorFarm([pool], FarmConfig(**cfg_kw), clock=clock,
                           metrics=MetricsRegistry())
    return farm, clock


def _win(t, f=2, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (t, f)).astype(np.float32)


# --------------------------------------------------------------------------- #
# batcher: bucketing, packing, flush policy
# --------------------------------------------------------------------------- #


def test_bucket_and_pad_helpers():
    assert bucket_for((6, 12), 4) == 6
    assert bucket_for((6, 12), 6) == 6
    assert bucket_for((6, 12), 7) == 12
    with pytest.raises(ValueError, match=r"registered lengths: \[6, 12\]"):
        bucket_for((6, 12), 13)
    w = pad_window(_win(3), 8)
    assert w.shape == (8, 2)
    assert np.all(w[3:] == 0) and np.array_equal(w[:3], _win(3))
    with pytest.raises(ValueError, match="exceeds bucket"):
        pad_window(_win(9), 8)
    assert [padded_batch_size(n, 64) for n in (1, 2, 3, 5, 33)] == \
        [1, 2, 4, 8, 64]
    with pytest.raises(ValueError, match="exceeds max_batch"):
        padded_batch_size(100, 64)              # pack splits groups first


def test_padded_batch_size_respects_the_cap_edge():
    # regression: B == max_batch must not round up past the cap, and an
    # over-cap B is a split-first error, never a silent over-cap dispatch
    assert padded_batch_size(63, 64) == 64
    assert padded_batch_size(64, 64) == 64
    with pytest.raises(ValueError, match="exceeds max_batch"):
        padded_batch_size(65, 64)


def test_pack_pads_batch_and_unpack_slices_back():
    reqs = [ServeRequest(rid=i, design="d", window=_win(3 + i, seed=i))
            for i in range(3)]
    [batch] = pack("d", 8, reqs, pad_batch=True, max_batch=64)
    assert batch.array.shape == (4, 8, 2)       # 3 real rows -> pow2 = 4
    assert batch.fill == 3 / 4
    assert np.all(batch.array[3] == 0)          # filler row
    out = np.arange(8, dtype=np.float32).reshape(4, 2)
    from repro.serving import unpack

    unpack(batch, out)
    for i, r in enumerate(reqs):
        assert np.array_equal(r.result, out[i])


@pytest.mark.parametrize("n", [63, 64, 65])
def test_pack_splits_at_the_max_batch_cap(n):
    # regression (B = 63 / 64 / 65 around cap 64): exactly max_batch real
    # rows never rounds up past the cap, and an overflowing group splits
    # into multiple MicroBatches instead of raising
    reqs = [ServeRequest(rid=i, design="d", window=_win(4, seed=i))
            for i in range(n)]
    batches = pack("d", 8, reqs, pad_batch=True, max_batch=64)
    assert [len(b.requests) for b in batches] == \
        ([63] if n == 63 else [64] if n == 64 else [64, 1])
    assert all(b.array.shape[0] <= 64 for b in batches)
    if n == 63:
        assert batches[0].array.shape[0] == 64      # pow2 pad up to cap
    if n == 64:
        assert batches[0].array.shape[0] == 64      # cap stays the cap
    if n == 65:
        assert batches[1].array.shape[0] == 1       # tail re-quantized
    # row i of each chunk still belongs to request i of that chunk
    got = [r.rid for b in batches for r in b.requests]
    assert got == list(range(n))


def test_batcher_form_splits_oversized_groups():
    # a single form() over > max_batch requests must produce only
    # cap-respecting dispatches (the old path raised from pack)
    mb = MicroBatcher(buckets={"d": (8,)}, max_batch=4, max_wait_s=0.0)
    reqs = [ServeRequest(rid=i, design="d", window=_win(4), t_submit=0.0)
            for i in range(9)]
    batches, linger = mb.form(reqs, now=0.0, flush=True)
    assert linger == []
    assert [len(b.requests) for b in batches] == [4, 4, 1]
    assert all(b.array.shape[0] <= 4 for b in batches)


def test_batcher_flush_policy():
    mb = MicroBatcher(buckets={"d": (8,)}, max_batch=4, max_wait_s=1.0)
    reqs = [ServeRequest(rid=i, design="d", window=_win(4), t_submit=0.0)
            for i in range(3)]
    batches, linger = mb.form(reqs, now=0.5)     # young partial: lingers
    assert batches == [] and [r.rid for r in linger] == [0, 1, 2]
    batches, linger = mb.form(reqs, now=1.5)     # oldest aged past linger
    assert len(batches) == 1 and linger == []
    reqs6 = [ServeRequest(rid=i, design="d", window=_win(4), t_submit=0.0)
             for i in range(6)]
    batches, linger = mb.form(reqs6, now=0.0)    # full batch always flushes
    assert len(batches) == 1 and len(batches[0].requests) == 4
    assert [r.rid for r in linger] == [4, 5]
    batches, _ = mb.form(reqs6, now=0.0, flush=True)
    assert sum(len(b.requests) for b in batches) == 6


# --------------------------------------------------------------------------- #
# queue: overflow shedding + deadline expiry
# --------------------------------------------------------------------------- #


def test_queue_sheds_at_capacity():
    clock = VirtualClock()
    q = AdmissionQueue(2, clock=clock, metrics=MetricsRegistry())
    reqs = [ServeRequest(rid=i, design="d", window=None) for i in range(4)]
    admitted = [q.offer(r) for r in reqs]
    assert admitted == [True, True, False, False]
    assert [r.status for r in reqs] == ["queued", "queued", SHED, SHED]
    assert all(r.error == "queue_full" for r in reqs[2:])
    assert q.metrics.counter("serving.queue.shed_full").value == 2


def test_queue_expires_on_deadline():
    clock = VirtualClock()
    q = AdmissionQueue(8, clock=clock, metrics=MetricsRegistry())
    hurried = ServeRequest(rid=0, design="d", window=None, deadline_s=1.0)
    patient = ServeRequest(rid=1, design="d", window=None)
    q.offer(hurried)
    q.offer(patient)
    clock.advance(2.0)
    expired = q.expire()
    assert expired == [hurried] and hurried.status == EXPIRED
    assert hurried.error == "deadline"
    assert q.peek() == [patient]                 # FIFO survivor intact


def test_queue_expires_at_exactly_the_deadline():
    # regression: a request inspected exactly AT its deadline can no
    # longer be answered in time — `now >= deadline` sheds it (the old
    # strict `>` dispatched it and then missed)
    clock = VirtualClock()
    q = AdmissionQueue(8, clock=clock, metrics=MetricsRegistry())
    req = ServeRequest(rid=0, design="d", window=None, deadline_s=1.0)
    q.offer(req)
    clock.advance(1.0)                           # now == deadline exactly
    assert q.expire() == [req]
    assert req.status == EXPIRED and req.error == "deadline"
    assert q.metrics.counter("serving.queue.expired").value == 1


class _SteppingClock:
    """A clock that advances ``step`` on every read — deterministically
    opens the take()→dispatch window the farm must re-check. Starts past
    zero so ``t_submit`` is never the 0.0 sentinel (which would make the
    queue re-stamp it with an extra clock read)."""

    def __init__(self, step=0.1, start=1.0):
        self.t = start
        self.step = step

    def __call__(self):
        t = self.t
        self.t += self.step
        return t


def test_farm_recheck_deadline_at_dispatch_time():
    # regression: a request can expire BETWEEN queue.take() and dispatch
    # (batch forming takes wall time); the farm must re-check at dispatch,
    # mark it expired under the same serving.queue.expired counter, and
    # never attach a result to it — while batchmates still complete.
    member = _Member()
    farm, clock = _fake_farm([member], clock=_SteppingClock(step=0.1))
    # clock reads: submit A -> 1.0, submit B -> 1.1, tick: expire -> 1.2
    # (A still alive: 1.2 < 1.35), form -> 1.3, dispatch -> 1.4 >= 1.35
    ra = farm.submit("fake", _win(4), deadline_s=1.35)
    rb = farm.submit("fake", _win(4))
    farm.tick(flush=True)
    a, b = farm.result(ra), farm.result(rb)
    assert a.status == EXPIRED and a.error == "deadline"
    assert a.result is None                      # missed SLO grows no result
    assert b.status == DONE and b.result is not None
    s = farm.stats()
    assert s.expired == 1 and s.done == 1 and s.failed == 0
    assert s.admitted == s.done + s.expired      # reconciliation holds
    assert member.calls == 1                     # batchmate still dispatched

    # the all-expired batch never reaches a member at all
    member2 = _Member()
    farm2, _ = _fake_farm([member2], clock=_SteppingClock(step=0.1))
    rid = farm2.submit("fake", _win(4), deadline_s=1.25)
    farm2.tick(flush=True)                       # expire 1.1 < 1.25, disp 1.3
    assert farm2.result(rid).status == EXPIRED
    assert member2.calls == 0
    s2 = farm2.stats()
    assert s2.dispatches == 0 and s2.expired == 1
    assert s2.admitted == s2.done + s2.expired


def test_farm_overflow_and_deadline_end_to_end():
    farm, clock = _fake_farm([_Member()], max_queue=2, max_batch=4)
    rids = [farm.submit("fake", _win(4)) for _ in range(4)]
    shed = [r for r in rids if farm.result(r).status == SHED]
    assert len(shed) == 2                        # bounded backpressure
    late = farm.submit("fake", _win(4))          # wait: queue is full too
    assert farm.result(late).status == SHED
    farm.run_until_drained()
    assert [farm.result(r).status for r in rids[:2]] == [DONE, DONE]

    farm, clock = _fake_farm([_Member()], max_queue=8)
    rid = farm.submit("fake", _win(4), timeout_s=1.0)
    clock.advance(5.0)
    farm.tick()
    assert farm.result(rid).status == EXPIRED
    s = farm.stats()
    assert s.expired == 1 and s.dispatches == 0  # never wasted a dispatch
    assert s.admitted == s.done + s.expired      # zero dropped invariant


def test_farm_unknown_design_and_oversized_window_shed_at_submit():
    farm, _ = _fake_farm([_Member()], lengths=(8,))
    r1 = farm.submit("nope", _win(4))
    assert farm.result(r1).status == SHED
    assert "unknown design" in farm.result(r1).error
    r2 = farm.submit("fake", _win(99))           # no bucket fits length 99
    assert farm.result(r2).status == SHED
    assert "no window bucket" in farm.result(r2).error


# --------------------------------------------------------------------------- #
# router: affinity + health + redispatch
# --------------------------------------------------------------------------- #


def test_router_prefers_member_holding_the_program():
    a, b = _Member(), _Member()
    b((np.zeros((4, 8, 2), np.float32)))         # b compiles (4, 8, 2)
    router = AffinityRouter([a, b], metrics=MetricsRegistry())
    i, m, hit = router.route((4, 8, 2), np.float32)
    assert (i, m, hit) == (1, b, True)
    i, _, hit = router.route((2, 8, 2), np.float32)   # nobody holds: miss
    assert hit is False
    assert router.metrics.counter("serving.router.affinity_hit").value == 1
    assert router.metrics.counter("serving.router.affinity_miss").value == 1


def test_router_health_gate_and_exhaustion():
    sick, well = _Member(healthy=False), _Member()
    router = AffinityRouter([sick, well], metrics=MetricsRegistry())
    for _ in range(4):
        i, _, _ = router.route((1, 8, 2), np.float32)
        assert i == 1                            # quarantined takes nothing
    with pytest.raises(NoServeableMember, match="no serveable member"):
        AffinityRouter([sick], metrics=MetricsRegistry()).route()
    with pytest.raises(NoServeableMember):
        router.route(exclude=(1,))               # well excluded, sick gated


def test_farm_redispatches_once_around_a_failing_member():
    bad, good = _Member(fail=True), _Member()
    farm, _ = _fake_farm([bad, good], max_batch=4)
    rids = [farm.submit("fake", _win(4)) for _ in range(2)]
    farm.run_until_drained()
    assert all(farm.result(r).status == DONE for r in rids)
    s = farm.stats()
    assert s.failed == 0 and s.redispatches >= 1
    assert good.calls >= 1

    # both members down: the batch fails loudly, not silently
    farm, _ = _fake_farm([_Member(fail=True), _Member(fail=True)],
                         max_batch=4)
    rid = farm.submit("fake", _win(4))
    farm.run_until_drained()
    assert farm.result(rid).status == "failed"
    assert farm.result(rid).error == "RuntimeError: member down"
    assert farm.stats().failed == 1


# --------------------------------------------------------------------------- #
# RTL bit-exactness + affinity retrace convergence (the tentpole claims)
# --------------------------------------------------------------------------- #


def test_microbatched_results_bit_exact_vs_per_request(lstm_exe):
    """Ragged windows, packed+padded into shared dispatches, must come back
    integer-identical to calling the deployment per padded window alone."""
    rng = np.random.default_rng(7)
    windows = [rng.standard_normal((t, 1)).astype(np.float32) * 0.5
               for t in (3, 4, 5, 6, 6, 4, 3, 5, 6, 2)]
    pool = DesignPool(family="lstm", members={6: [lstm_exe]})
    farm = AcceleratorFarm([pool], FarmConfig(max_batch=8),
                           metrics=MetricsRegistry())
    rids = [farm.submit("lstm", w) for w in windows]
    farm.run_until_drained()
    for rid, w in zip(rids, windows):
        req = farm.result(rid)
        assert req.status == DONE and req.bucket_len == 6
        solo = np.asarray(lstm_exe(pad_window(w, 6)[None]))[0]
        assert np.array_equal(np.asarray(req.result), solo), rid


def test_affinity_keeps_retraces_bounded(lstm_exe):
    """Steady mixed traffic converges to a stable shape->member assignment:
    after a warm epoch, more identical traffic compiles NOTHING new."""
    replica = dataclasses.replace(lstm_exe)      # fresh emulator
    pool = DesignPool(family="lstm", members={6: [lstm_exe, replica]})
    farm = AcceleratorFarm([pool], FarmConfig(max_batch=8),
                           metrics=MetricsRegistry())

    def epoch(seed):
        rng = np.random.default_rng(seed)
        for t in rng.integers(2, 7, size=24):
            farm.submit("lstm", rng.standard_normal(
                (int(t), 1)).astype(np.float32))
        farm.run_until_drained()

    epoch(0)
    warm = lstm_exe.emulator.trace_count + replica.emulator.trace_count
    assert warm > 0
    epoch(1)                                     # same shape universe
    cold = lstm_exe.emulator.trace_count + replica.emulator.trace_count
    assert cold == warm                          # zero new retraces
    s = farm.stats()
    assert s.affinity_hits > 0
    assert s.failed == 0 and s.admitted == s.done


def test_executable_holds_program_probe(lstm_exe):
    replica = dataclasses.replace(lstm_exe)
    x = np.zeros((4, 6, 1), np.float32)
    assert not replica.holds_program(x.shape, x.dtype)
    replica(x)
    assert replica.holds_program(x.shape, x.dtype)
    assert replica.emulator.has_program(x.shape, x.dtype, float_io=True)
    assert not replica.emulator.has_program(x.shape, np.int32)
    assert not replica.holds_program((2, 6, 1), x.dtype)


# --------------------------------------------------------------------------- #
# loadgen: determinism + zero-loss accounting
# --------------------------------------------------------------------------- #


def _loadgen_once():
    from repro.serving import loadgen

    clock = VirtualClock()
    farm, pools = loadgen.build_farm(
        ("lstm",), replicas=1, buckets={"lstm": (6,)},
        cfg=FarmConfig(max_batch=8), seed=0, clock=clock,
        metrics=MetricsRegistry())
    spec = loadgen.TrafficSpec(archs=("lstm",), n_requests=24, wave=8,
                               seed=3)
    return loadgen.run_loadgen(farm, pools, spec, clock=clock)


def test_loadgen_seeded_runs_are_identical():
    a = json.dumps(_loadgen_once(), indent=2, sort_keys=True)
    b = json.dumps(_loadgen_once(), indent=2, sort_keys=True)
    assert a == b
    rep = json.loads(a)
    assert rep["submitted"] == 24
    assert rep["by_status"] == {"done": 24}
    assert rep["dropped_after_admission"] == 0
    assert rep["per_design"]["lstm"]["gop_per_j"] > 0   # cycle-model energy


def test_loadgen_open_loop_sheds_under_overload():
    from repro.serving import loadgen

    clock = VirtualClock()
    farm, pools = loadgen.build_farm(
        ("lstm",), replicas=1, buckets={"lstm": (6,)},
        cfg=FarmConfig(max_batch=8, max_queue=8), seed=0, clock=clock,
        metrics=MetricsRegistry())
    spec = loadgen.TrafficSpec(archs=("lstm",), n_requests=64, wave=32,
                               mode="open", seed=1)
    rep = loadgen.run_loadgen(farm, pools, spec, clock=clock)
    assert rep["by_status"].get("shed", 0) > 0   # the queue was the brake
    assert rep["dropped_after_admission"] == 0   # but nothing vanished
    total = sum(rep["by_status"].values())
    assert total == rep["submitted"] == 64


def test_loadgen_cli_smoke(tmp_path, monkeypatch):
    from repro.serving.loadgen import main

    # main() turns on the persistent compile cache unless this variable is
    # set; set, it leaves this process's JAX config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    out = tmp_path / "bench.json"
    rc = main(["--arch", "lstm", "--requests", "16", "--wave", "8",
               "--replicas", "1", "--max-batch", "8",
               "--out", str(out), "--p99-bound", "60"])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["by_status"] == {"done": 16}
    assert rep["dropped_after_admission"] == 0


# --------------------------------------------------------------------------- #
# measure(): warmup runs must not skew the latency percentiles
# --------------------------------------------------------------------------- #


def _slow_start_fn(slow_calls, slow_s=0.02):
    import time as _time

    state = {"n": 0}

    def fn(x):
        state["n"] += 1
        if state["n"] <= slow_calls:
            _time.sleep(slow_s)
        return x

    fn.state = state
    return fn


def test_measure_percentiles_exclude_warmup():
    from repro.core.target import XLADeployment

    x = np.zeros(4, np.float32)
    dep = XLADeployment(fn=_slow_start_fn(3))
    rep = dep.measure((x,), model="m", model_flops=1e6, n_runs=10,
                      warmup=3)
    assert dep.fn.state["n"] == 13               # warmup runs DID execute
    assert rep.latency_p99_s < 0.02              # ...but never entered p99

    # control: same deployment shape, warmup disabled -> the slow first
    # calls land in the samples and the tail blows up (the old bug's shape)
    dep0 = XLADeployment(fn=_slow_start_fn(3))
    rep0 = dep0.measure((x,), model="m", model_flops=1e6, n_runs=10,
                        warmup=0)
    assert rep0.latency_p99_s >= 0.015


def test_protocol_routes_warmup_into_measure():
    from repro.core.report import MeasurementReport
    from repro.core.target import Deployment
    from repro.verify.protocol import MeasurementProtocol, run_protocol

    seen = {}

    class _Dep(Deployment):
        target = "fake"

        def __call__(self, *a):
            return a

        def measure(self, args, *, model, model_flops, n_runs=1,
                    warmup=1, hw=None):
            seen.update(n_runs=n_runs, warmup=warmup)
            return MeasurementReport(
                model=model, platform="fake", latency_s=1e-3,
                power_w=0.1, energy_j=1e-4, gop_per_j=1.0,
                n_runs=n_runs, target=self.target)

    rep = run_protocol(_Dep(), (np.zeros(2),), model="m", model_flops=1e6,
                       protocol=MeasurementProtocol(warmup=5, n_runs=2))
    assert seen == {"n_runs": 2, "warmup": 5}
    assert rep.warmup == 5 and rep.passed


# --------------------------------------------------------------------------- #
# sharding: bit-exact on 1 device, real split in a forced-device subprocess
# --------------------------------------------------------------------------- #


def test_program_lru_shared_and_thread_safe(lstm_exe):
    # regression: shard.py re-implemented the compiled-program LRU without
    # the lock PR 7 added to the emulator — both must now share the one
    # locked ProgramLRU helper, and it must stay consistent under the
    # farm's concurrent dispatch pattern.
    import threading

    from repro.rtl.program_cache import ProgramLRU
    from repro.serving import ShardedExecutable, make_serving_mesh

    sharded = ShardedExecutable(dataclasses.replace(lstm_exe),
                                make_serving_mesh(1))
    assert isinstance(sharded._programs, ProgramLRU)
    assert isinstance(lstm_exe.emulator._programs, ProgramLRU)

    lru = ProgramLRU(max_programs=2)
    built = []
    errors = []

    def hammer(tid):
        try:
            for i in range(200):
                key = ("k", i % 3)

                def factory(key=key):
                    built.append(key)
                    return key

                prog, _hit, _ev = lru.get_or_build(key, factory)
                assert prog == key          # never another key's program
        except Exception as e:              # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    st = lru.stats()
    assert st["hits"] + st["misses"] == 4 * 200
    assert st["misses"] == len(built)       # every miss built exactly once
    assert st["size"] <= 2                  # eviction bound respected


def test_sharded_executable_bit_exact_single_device(lstm_exe):
    from repro.serving import ShardedExecutable, make_serving_mesh

    sharded = ShardedExecutable(dataclasses.replace(lstm_exe),
                                make_serving_mesh(1))
    x = np.random.default_rng(5).standard_normal(
        (4, 6, 1)).astype(np.float32) * 0.5
    assert np.array_equal(np.asarray(sharded(x)),
                          np.asarray(lstm_exe(x)))
    assert sharded.holds_program(x.shape, x.dtype)
    # odd batch pads up to a shard multiple and slices back
    x3 = x[:3]
    assert np.array_equal(np.asarray(sharded(x3)),
                          np.asarray(lstm_exe(x3)))


def test_sharded_executable_multidevice_bit_exact():
    """4 forced host devices: the sharded dispatch must still be integer-
    identical to the unsharded emulator (subprocess so the main test
    process keeps seeing 1 device)."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=4")
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {ROOT + "/src"!r})
        import dataclasses
        import jax
        import numpy as np
        from repro.configs.elastic_lstm import config
        from repro.model.layers import init_params
        from repro.model.lstm import lstm_schema
        from repro.rtl.backend import translate_rtl
        from repro.serving import ShardedExecutable, make_serving_mesh

        assert len(jax.devices()) == 4
        cfg = config()
        params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
        _, exe = translate_rtl(cfg, params)
        sharded = ShardedExecutable(dataclasses.replace(exe),
                                    make_serving_mesh(4))
        x = np.random.default_rng(5).standard_normal(
            (8, 6, 1)).astype(np.float32) * 0.5
        y = np.asarray(sharded(x))
        y_ref = np.asarray(exe(x))
        assert np.array_equal(y, y_ref), np.abs(y - y_ref).max()
        print("sharded-bit-exact-ok", y.shape)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "sharded-bit-exact-ok" in r.stdout
