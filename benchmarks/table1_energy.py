"""Reproduction of the paper's Table I (XC7S15 @ 100 MHz, LSTM accelerator).

The paper's claim: the workflow's *estimation* stage tracks hardware
*measurement* closely (power 70 vs 71 mW, latency 53.32 vs 57.25 µs,
efficiency 5.04 vs 5.33 GOP/J).

We reproduce the three-row structure with our pipeline:
  row 1 — paper's Vivado estimation        (constants from the paper)
  row 2 — paper's Elastic-Node measurement (constants from the paper)
  row 3 — OUR stage-2 estimate, read off the *generated accelerator*: the
          RTL backend lowers the LSTM to template artifacts and the
          synthesized design's cycle schedule + duty-cycled XC7S15 power
          model produce latency/power/GOP/J (DESIGN.md §5–§6).
The reproduction check: row 3 must sit within ~10 % of row 2, the same
accuracy band the paper demonstrates for its own estimator.
"""
from __future__ import annotations

import time

import jax

from repro.configs import get_config
from repro.energy.hw import XC7S15
from repro.model.layers import init_params
from repro.model.lstm import lstm_apply, lstm_flops, lstm_schema

# Table I constants (from the paper)
PAPER_EST = {"power_mw": 70.0, "latency_us": 53.32, "gop_j": 5.04}
PAPER_MEAS = {"power_mw": 71.0, "latency_us": 57.25, "gop_j": 5.33}


def our_estimate():
    """Stage-2 estimate from the RTL backend's generated artifacts."""
    from repro.rtl import emit_graph, lower_model, synthesize

    cfg = get_config("elastic-lstm")
    params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
    graph = lower_model(cfg, params)
    artifacts = emit_graph(graph)
    rep = synthesize(graph, hw=XC7S15, model_flops=float(lstm_flops(cfg)),
                     n_artifacts=len(artifacts))
    return {"power_mw": rep.est_power_w * 1e3,
            "latency_us": rep.est_latency_s * 1e6,
            "gop_j": rep.est_gop_per_j,
            "artifacts": len(artifacts),
            "cycles": rep.resources["cycles"]}


def container_measurement(n: int = 200):
    """Wall-clock of the same graph on the container (sanity, not FPGA)."""
    cfg = get_config("elastic-lstm")
    params = init_params(lstm_schema(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 1))
    fn = jax.jit(lambda p, xx: lstm_apply(p, xx, cfg)[0])
    fn(params, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(params, x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / n


def run() -> dict:
    est = our_estimate()
    cpu_us = container_measurement() * 1e6
    rows = [("paper_vivado_est", PAPER_EST), ("paper_node_meas", PAPER_MEAS),
            ("our_stage2_est", est)]
    print(f"(row 3 generated from {est['artifacts']} RTL artifacts, "
          f"{est['cycles']} cycles @ 100 MHz)")
    print(f"{'row':>18} {'power(mW)':>10} {'time(us)':>9} {'GOP/J':>7}")
    for name, r in rows:
        print(f"{name:>18} {r['power_mw']:10.1f} {r['latency_us']:9.2f} "
              f"{r['gop_j']:7.2f}")
    lat_err = (est["latency_us"] - PAPER_MEAS["latency_us"]) \
        / PAPER_MEAS["latency_us"]
    eff_err = (est["gop_j"] - PAPER_MEAS["gop_j"]) / PAPER_MEAS["gop_j"]
    paper_err = (PAPER_EST["latency_us"] - PAPER_MEAS["latency_us"]) \
        / PAPER_MEAS["latency_us"]
    print(f"our est vs paper meas: latency {lat_err:+.1%}, "
          f"GOP/J {eff_err:+.1%}  (paper's own est err: {paper_err:+.1%})")
    print(f"container wall-clock (jit, not FPGA): {cpu_us:.1f} us/inference")
    return {"our_est": est, "lat_err": lat_err, "eff_err": eff_err,
            "cpu_us": cpu_us}


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    run()
