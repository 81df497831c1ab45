"""Tier-1: the observability layer (repro.obs) — spans, metrics, artifacts.

Everything runs on an injectable fake clock, so span trees and durations
are exact, not flaky-wall-clock assertions; the profiler mode is read
back from the XSpace a ``jax.profiler`` trace writes.
"""
import glob
import json
import os
import warnings

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, RunTrace, Tracer,
                       ancestors, capture, children_of, find_spans, get_metrics,
                       get_tracer, percentile, set_tracer, span_tree,
                       to_chrome_trace, to_jsonl)


class FakeClock:
    """Deterministic monotonic clock: each read advances by ``step``."""

    def __init__(self, step: float = 1.0):
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# --------------------------------------------------------------------------- #
# Tracer: nesting, determinism, disabled path
# --------------------------------------------------------------------------- #


def test_span_nesting_deterministic_tree():
    trc = Tracer(clock=FakeClock())
    with trc.span("root", knob=8):
        with trc.span("child_a", mode="fused"):
            pass
        with trc.span("child_b") as b:
            b.set_attrs(found=True)
            with trc.span("grand"):
                pass
    assert len(trc.spans) == 4
    root = find_spans(trc.spans, "root")[0]
    a = find_spans(trc.spans, "child_a")[0]
    b = find_spans(trc.spans, "child_b")[0]
    g = find_spans(trc.spans, "grand")[0]
    # parentage encodes the lexical nesting
    assert root.parent_id is None
    assert a.parent_id == root.span_id
    assert b.parent_id == root.span_id
    assert g.parent_id == b.span_id
    # fake clock: every read advances by exactly 1
    assert root.start == 1.0 and root.end == 8.0
    assert a.duration == 1.0
    # attrs: at-creation and mid-span both land
    assert root.attrs == {"knob": 8}
    assert b.attrs == {"found": True}
    # tree helpers agree
    assert [(s.name, d) for s, d in span_tree(trc.spans)] == [
        ("root", 0), ("child_a", 1), ("child_b", 1), ("grand", 2)]
    assert [s.name for s in children_of(trc.spans, root)] == [
        "child_a", "child_b"]
    assert [s.name for s in ancestors(trc.spans, g)] == ["child_b", "root"]


def test_span_ids_unique_and_exception_safe():
    trc = Tracer(clock=FakeClock())
    with pytest.raises(ValueError):
        with trc.span("outer"):
            with trc.span("inner"):
                raise ValueError("boom")
    # both spans still closed and recorded; stack unwound
    assert sorted(s.name for s in trc.spans) == ["inner", "outer"]
    assert not trc._stack
    ids = [s.span_id for s in trc.spans]
    assert len(ids) == len(set(ids))


def test_disabled_tracer_records_nothing():
    trc = Tracer(enabled=False)
    with trc.span("nope", big=list(range(100))) as s:
        s.set_attrs(more=1)          # null span swallows attrs
    assert trc.spans == []
    # the disabled path hands back one shared object (no per-call alloc)
    assert trc.span("a") is trc.span("b")


def test_process_default_disabled_and_swappable():
    assert get_tracer().enabled is False       # default: opt-in only
    mine = Tracer(clock=FakeClock())
    prev = set_tracer(mine)
    try:
        assert get_tracer() is mine
    finally:
        set_tracer(prev)
    assert get_tracer() is prev


# --------------------------------------------------------------------------- #
# Metrics: counters, gauges, histogram percentiles vs numpy
# --------------------------------------------------------------------------- #


def test_counter_and_gauge():
    c = Counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert c.snapshot() == {"type": "counter", "value": 5}
    g = Gauge("g")
    assert g.snapshot()["n"] == 0
    for v in (3.0, -1.0, 7.0):
        g.set(v)
    assert (g.value, g.min, g.max, g.n) == (7.0, -1.0, 7.0, 3)


@pytest.mark.parametrize("p", [0, 25, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy(p):
    rng = np.random.default_rng(0)
    for n in (1, 2, 5, 100):
        vals = rng.normal(size=n).tolist()
        assert percentile(vals, p) == pytest.approx(
            float(np.percentile(vals, p)), abs=1e-12)


def test_percentile_empty_is_zero():
    assert percentile([], 99) == 0.0
    h = Histogram("empty")
    assert h.summary() == {"count": 0, "sum": 0.0, "mean": 0.0, "min": 0.0,
                           "max": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}


def test_histogram_summary():
    h = Histogram("lat")
    for v in range(1, 101):          # 1..100
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == pytest.approx(float(np.percentile(h.values, 50)))
    assert s["p99"] == pytest.approx(float(np.percentile(h.values, 99)))


def test_registry_get_or_create_and_snapshot():
    mx = MetricsRegistry()
    assert mx.counter("a") is mx.counter("a")
    mx.counter("z.count").inc(2)
    mx.gauge("a.depth").set(3)
    mx.histogram("m.lat").observe(0.5)
    snap = mx.snapshot()
    assert list(snap) == sorted(snap)            # stable artifact ordering
    assert snap["z.count"]["value"] == 2
    assert snap["m.lat"]["count"] == 1
    mx.reset()
    assert mx.snapshot() == {}


# --------------------------------------------------------------------------- #
# Exporters: Chrome trace, JSONL
# --------------------------------------------------------------------------- #


def _sample_spans():
    trc = Tracer(clock=FakeClock(0.25))
    with trc.span("root", arch="elastic-lstm"):
        with trc.span("child", mode="fused", cached=True):
            pass
    return trc.spans


def test_chrome_trace_schema_and_roundtrip():
    spans = _sample_spans()
    doc = json.loads(json.dumps(to_chrome_trace(spans)))  # through JSON
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0           # µs, rebased
        assert {"name", "pid", "tid", "args"} <= set(ev)
    # the tree survives the JSON round trip: span/parent ids ride in args
    by_name = {ev["name"]: ev for ev in doc["traceEvents"]}
    for orig in spans:
        ev = by_name[orig.name]
        assert ev["dur"] == pytest.approx(orig.duration * 1e6)
        assert ev["args"]["span_id"] == orig.span_id
        assert ev["args"].get("parent_id") == orig.parent_id
        assert {k: ev["args"][k] for k in orig.attrs} == orig.attrs


def test_jsonl_one_object_per_span():
    spans = _sample_spans()
    lines = to_jsonl(spans).splitlines()
    assert len(lines) == len(spans)
    objs = [json.loads(ln) for ln in lines]
    assert {o["name"] for o in objs} == {"root", "child"}
    assert to_jsonl([]) == ""


def test_nonserializable_attrs_degrade_to_repr():
    trc = Tracer(clock=FakeClock())
    with trc.span("s", shape=(1, 6, 1)):
        pass
    doc = to_chrome_trace(trc.spans)
    json.dumps(doc)                  # must be JSON-clean
    assert doc["traceEvents"][0]["args"]["shape"] == repr((1, 6, 1))


# --------------------------------------------------------------------------- #
# capture + RunTrace artifact
# --------------------------------------------------------------------------- #


def test_capture_installs_and_restores(tmp_path):
    prev_trc, prev_mx = get_tracer(), get_metrics()
    with capture("unit", clock=FakeClock()) as cap:
        assert get_tracer() is cap.tracer and get_tracer().enabled
        with get_tracer().span("work", k=1):
            get_metrics().counter("n.things").inc(3)
            get_metrics().histogram("lat").observe(0.5)
    assert get_tracer() is prev_trc and get_metrics() is prev_mx
    rt = cap.trace
    assert rt.name == "unit"
    assert [s.name for s in rt.spans] == ["work"]
    assert rt.metrics["n.things"]["value"] == 3

    paths = rt.save(str(tmp_path / "build"))
    with open(paths["trace.json"]) as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["name"] == "work"
    with open(paths["trace.jsonl"]) as f:
        assert json.loads(f.readline())["name"] == "work"
    with open(paths["metrics.json"]) as f:
        assert json.load(f)["lat"]["count"] == 1
    text = (tmp_path / "build" / "summary.txt").read_text()
    assert "work" in text and "n.things" in text


def test_capture_restores_on_exception():
    prev = get_tracer()
    with pytest.raises(RuntimeError):
        with capture("boom"):
            raise RuntimeError("x")
    assert get_tracer() is prev


def test_runtrace_summary_depth_cap():
    trc = Tracer(clock=FakeClock())
    with trc.span("lvl0"):
        with trc.span("lvl1"):
            with trc.span("lvl2"):
                pass
    rt = RunTrace(name="deep", spans=list(trc.spans))
    assert "lvl2" in rt.summary()
    assert "lvl2" not in rt.summary(max_depth=1)


# --------------------------------------------------------------------------- #
# Profiler mode: spans on the JAX profiler's clock, read back from the XSpace
# --------------------------------------------------------------------------- #


def _host_events(log_dir):
    """``(line, name, start_ns, end_ns, stats)`` of every host-plane event
    of the one XSpace under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    with warnings.catch_warnings():  # jaxlib's stats type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for i, line in enumerate(plane.lines):
                    out.extend((i, e.name, e.start_ns, e.end_ns,
                                dict(e.stats)) for e in line.events)
    return out


def _within(outer, inner):
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


def test_profiler_spans_land_in_the_xspace_not_in_memory(tmp_path):
    import jax

    trc = Tracer(profiler=True)
    with jax.profiler.trace(str(tmp_path)):
        with trc.span("outer", k=3) as s:
            s.set_attrs(found=True)
            with trc.span("inner"):
                pass
    assert trc.spans == []           # the XSpace is the store
    evs = {e[1]: e for e in _host_events(str(tmp_path))}
    outer, inner = evs["outer"], evs["inner"]
    assert outer[4] == {"k": 3, "found": 1}
    assert _within(outer, inner)


@pytest.fixture(scope="module")
def deployment():
    """An elastic-lstm RTL deployment at test size, its batch-2 program
    compiled."""
    from repro.energy.hw import XC7S15
    from repro.rtl.backend import RTLExecutable
    from repro.verify.vectors import canonical_graph

    dep = RTLExecutable(graph=canonical_graph("elastic-lstm")[0],
                        artifacts={}, hw=XC7S15)
    x = np.random.default_rng(0).normal(size=(2, 6, 1)).astype(np.float32)
    want = np.asarray(dep(x))
    return dep, x, want


def test_deployment_call_is_one_span_tree_on_the_profiler_clock(
        tmp_path, deployment):
    import jax

    dep, x, want = deployment
    x3 = np.concatenate([x, x[:1]])
    prev = set_tracer(Tracer(profiler=True))
    try:
        with jax.profiler.trace(str(tmp_path)):
            got = np.asarray(dep(x))
            dep(x3)                  # a new shape compiles in the window
    finally:
        set_tracer(prev)
    np.testing.assert_array_equal(got, want)     # spans change no result
    evs = sorted((e for e in _host_events(str(tmp_path))
                  if e[1].startswith("rtl.")), key=lambda e: e[2])
    calls = [e for e in evs if e[1] == "rtl.call"]
    assert [c[4]["batch"] for c in calls] == [2, 3]
    for call, cached in zip(calls, (1, 0)):
        inside = [e for e in evs if e is not call and _within(call, e)]
        assert [e[1] for e in inside] == ["rtl.emulator.dispatch"]
        assert inside[0][4]["cached"] == cached
        assert inside[0][4]["mode"] == "fused"
        assert inside[0][4]["io"] == "float"


def test_disabled_tracer_leaves_no_annotation(tmp_path, deployment):
    import jax

    dep, x, want = deployment
    off = Tracer(enabled=False, profiler=True)
    assert get_tracer().enabled is False
    with jax.profiler.trace(str(tmp_path)):
        with off.span("nope", k=1) as s:
            s.set_attrs(more=2)
            got = np.asarray(dep(x))
    np.testing.assert_array_equal(got, want)
    assert off.span("a") is off.span("b")        # the shared null span
    names = {e[1] for e in _host_events(str(tmp_path))}
    assert "nope" not in names
    assert not {n for n in names if n.startswith("rtl.")}
