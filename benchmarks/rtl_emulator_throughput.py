"""RTL-emulator throughput: fused single-dispatch executor vs per-step.

The emulator is the inner loop of the whole Creator workflow (every generated
accelerator is verified/measured against it), so its throughput gates design
iteration. This benchmark sweeps batch × the paper's seq-6 window on the
elastic-lstm design and times

* ``fused``    — the staged executor (one fused int LSTM kernel dispatch per
  cell per window, jitted graph walk, weight-resident device constants);
* ``per_step`` — the pre-fusion schedule (one interpreted MAC ``pallas_call``
  per timestep from an un-jitted Python walk), the PR-1 baseline.

The ``multi_design`` section times the DSE turnaround (DESIGN.md §15): K
isomorphic weight-perturbed candidates emulated end-to-end — construct +
trace + compile + run, the cost a design-space search actually pays per
candidate set — sequentially (one fresh emulator per design, the pre-PR-10
world) vs batched (one vmapped program over the stacked design axis), with
a bit-exactness cross-check against the sequential ``fused`` outputs.

Writes ``BENCH_rtl_emulator.json`` (the perf trajectory artifact; CI uploads
it on every push).
"""
from __future__ import annotations

import argparse
import json
import time

DEFAULT_BATCHES = (1, 32, 256)
SEQ = 6


def _timeit(fn, n: int) -> float:
    """Mean µs/call over n calls (fn must block on its own result)."""
    fn()                                     # warm: compile/trace once
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def run(batches=DEFAULT_BATCHES, *, n_fused: int = 20, n_per_step: int = 3,
        out: str = "BENCH_rtl_emulator.json") -> dict:
    import jax

    from repro.configs import get_config
    from repro.core.creator import Creator
    from repro.core.types import SHAPES_LSTM
    from repro.energy.hw import XC7S15
    from repro.rtl import RTLEmulator

    cr = Creator(hw=XC7S15)
    st = cr.build(get_config("elastic-lstm"), SHAPES_LSTM["infer_1"])
    _, exe = cr.translate(st, target="rtl")
    fused = exe.emulator                     # staged executor, mode="fused"
    per_step = RTLEmulator(exe.graph, mode="pallas")   # PR-1 schedule

    rows = []
    for batch in batches:
        x = jax.random.normal(jax.random.PRNGKey(0), (batch, SEQ, 1))
        s0 = fused.cache_stats()             # obs counters, per-batch delta
        fused_us = _timeit(
            lambda: jax.block_until_ready(fused.run(x).outputs), n_fused)
        s1 = fused.cache_stats()
        per_step_us = _timeit(
            lambda: jax.block_until_ready(
                per_step.run_per_step(x).outputs), n_per_step)
        row = {
            "batch": batch, "seq": SEQ,
            "fused_us": round(fused_us, 1),
            "per_step_us": round(per_step_us, 1),
            "speedup": round(per_step_us / fused_us, 2),
            "fused_us_per_window": round(fused_us / batch, 2),
            # program-cache behavior over this batch's timed calls: one
            # miss+retrace for the new shape, hits for every other call
            "cache_hits": s1["hits"] - s0["hits"],
            "cache_misses": s1["misses"] - s0["misses"],
            "retraces": s1["retraces"] - s0["retraces"],
        }
        rows.append(row)
        print(f"batch={batch:>4} seq={SEQ}: fused {fused_us:>10.1f} us  "
              f"per-step {per_step_us:>12.1f} us  "
              f"x{row['speedup']:.1f}  ({row['fused_us_per_window']:.2f} "
              f"us/window)  cache {row['cache_hits']}h/"
              f"{row['cache_misses']}m/{row['retraces']}t")

    stats = fused.cache_stats()
    result = {
        "design": "elastic-lstm",
        "backend": jax.default_backend(),
        "trace_count": fused.trace_count,    # == len(batches): one per shape
        "cache": {"hits": stats["hits"], "misses": stats["misses"],
                  "evictions": stats["evictions"],
                  "dispatches": stats["dispatches"]},
        "rows": rows,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {out}")
    return result


def run_multi(k: int = 32, *, batch: int = 8,
              archs=("elastic-lstm", "elastic-conv1d")) -> list:
    """The multi-design turnaround benchmark: K isomorphic candidates,
    sequential fresh-emulator evaluation vs one vmapped dispatch."""
    import jax
    import numpy as np

    from repro.rtl import MultiDesignEmulator, RTLEmulator
    from repro.verify.vectors import canonical_graph

    rows = []
    for arch in archs:
        graphs = [canonical_graph(arch, seed=s)[0] for s in range(k)]
        in_shape = graphs[0].edges[graphs[0].inputs[0]].shape
        x = np.random.default_rng(0).integers(
            -8, 8, (batch,) + in_shape).astype(np.int32)

        # sequential per-design: the pre-sharing world — every candidate
        # pays its own staging + trace + compile (mode "fused", the
        # production default), which is what bounded DSE turnaround
        t0 = time.perf_counter()
        seq_outs = []
        for g in graphs:
            em = RTLEmulator(g, mode="fused")
            seq_outs.append(np.asarray(
                jax.block_until_ready(em.run_int(x).outputs), np.int64))
        seq_s = time.perf_counter() - t0
        seq_outs = np.stack(seq_outs)

        # batched: stage all K, trace + compile ONE vmapped program, run
        t0 = time.perf_counter()
        multi = MultiDesignEmulator(graphs)
        out = np.asarray(jax.block_until_ready(
            multi.run_int(x).outputs), np.int64)
        vmap_s = time.perf_counter() - t0
        warm_us = _timeit(
            lambda: jax.block_until_ready(multi.run_int(x).outputs), 10)

        row = {
            "arch": arch, "k": k, "batch": batch,
            "sequential_s": round(seq_s, 3),
            "vmapped_s": round(vmap_s, 3),
            "speedup": round(seq_s / vmap_s, 2),
            "vmapped_warm_us": round(warm_us, 1),
            "vmapped_traces": multi.trace_count,
            "bit_exact_vs_sequential_fused":
                bool(np.array_equal(out, seq_outs)),
        }
        rows.append(row)
        print(f"multi_design {arch}: k={k} sequential {seq_s:.2f}s  "
              f"vmapped {vmap_s:.2f}s  x{row['speedup']:.1f}  "
              f"warm {warm_us:.0f} us/dispatch  "
              f"bit_exact={row['bit_exact_vs_sequential_fused']}")
    return rows


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, nargs="+", default=None,
                   help="batch sizes to sweep (default: 1 32 256)")
    p.add_argument("--n", type=int, default=20,
                   help="timed iterations for the fused path")
    p.add_argument("--multi-k", type=int, default=32,
                   help="candidate count for the multi_design section "
                        "(0 to skip)")
    p.add_argument("--out", default="BENCH_rtl_emulator.json",
                   help="output JSON path ('' to skip writing)")
    a = p.parse_args()
    result = run(tuple(a.batch) if a.batch else DEFAULT_BATCHES,
                 n_fused=a.n, out="")
    if a.multi_k:
        result["multi_design"] = run_multi(a.multi_k)
    if a.out:
        with open(a.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {a.out}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
