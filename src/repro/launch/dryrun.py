import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
    # memory-minimizing list scheduler: the CPU default overlaps remat chunks
    # concurrently, inflating temp_size ~5x vs what a TPU schedule would hold
    + " --xla_cpu_enable_concurrency_optimized_scheduler=false")
"""Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers, compiles,
fits, and report its roofline terms — without touching real hardware.

This is the TPU analogue of the paper's Stage-2 ("synthesize in Vivado,
read the estimation reports"): ``jax.jit(...).lower().compile()`` is our
synthesis, ``memory_analysis()`` the resource-utilization report and
``cost_analysis()`` + the collective parse the timing/power estimation.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json DIR]
"""
import argparse
import dataclasses
import json
import sys
import time
from typing import Optional
# (dataclasses used for ParallelismConfig.replace in extrapolate mode)

import jax

from repro.configs import ALL_IDS, get_config
# model_flops_estimate moved to repro.core.target (so the Creator/targets can
# import it without this module's XLA_FLAGS side effect); re-exported here
# for callers that learned the old address.
from repro.core.target import model_flops_estimate  # noqa: F401
from repro.core.types import ParallelismConfig, shape_table_for, shapes_for
from repro.energy.roofline import HEADER, RooflineReport, roofline
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.model.lm import Stepper


def _compile_cell(cfg, shape, mcfg, mesh, par):
    """One lower+compile; returns (cost_dict, mem_stats, hlo_text, seconds)."""
    from jax.sharding import NamedSharding
    from repro.model.layers import tree_map_pspec
    from repro.model.lm import batch_pspecs
    from repro.optim.adamw import opt_state_schema

    st = Stepper(cfg, shape, mcfg, par, mesh=mesh)
    t0 = time.perf_counter()
    param_sh = st.shardings(st.schema)
    bspecs = batch_pspecs(cfg, shape, mcfg)
    batch_sh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
    abstract = st.abstract_inputs()

    if cfg.family in ("lstm", "conv1d") and shape.kind != "train":
        # the paper's serving workloads: plain forward inference
        if cfg.family == "lstm":
            from repro.model.lstm import lstm_apply as window_apply
        else:
            from repro.model.conv1d import conv1d_apply as window_apply

        with mesh:
            ab = dict(abstract["batch"])
            ab.pop("y", None)
            bsh = dict(batch_sh)
            bsh.pop("y", None)
            fn = jax.jit(lambda p, b: window_apply(p, b["x"], cfg)[0],
                         in_shardings=(param_sh, bsh))
            lowered = fn.lower(abstract["params"], ab)
            compiled = lowered.compile()
        from repro.energy.roofline import normalize_cost

        return (normalize_cost(compiled.cost_analysis()),
                compiled.memory_analysis(),
                compiled.as_text(), time.perf_counter() - t0)

    with mesh:
        if shape.kind == "train":
            opt_sh = tree_map_pspec(lambda s: NamedSharding(mesh, s.pspec),
                                    opt_state_schema(st.schema, mcfg))
            fn = jax.jit(st.train_fn(),
                         in_shardings=(param_sh, opt_sh, batch_sh),
                         donate_argnums=(0, 1))
            lowered = fn.lower(abstract["params"], abstract["opt_state"],
                               abstract["batch"])
        elif shape.kind == "prefill":
            fn = jax.jit(st.prefill_fn(), in_shardings=(param_sh, batch_sh))
            lowered = fn.lower(abstract["params"], abstract["batch"])
        else:  # decode
            cache_sh = tree_map_pspec(
                lambda s: NamedSharding(mesh, s.pspec), st.cache_schema())
            fn = jax.jit(st.decode_fn(),
                         in_shardings=(param_sh, batch_sh["tokens"], cache_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(abstract["params"], abstract["batch"]["tokens"],
                               abstract["cache"])
        compiled = lowered.compile()
    dt = time.perf_counter() - t0
    from repro.energy.roofline import normalize_cost

    return (normalize_cost(compiled.cost_analysis()),
            compiled.memory_analysis(), compiled.as_text(), dt)


def extrapolation_plan(cfg):
    """[(cfg_L, weight)] s.t. cost(cfg) = Σ w_i · cost(cfg_L_i).

    Per-layer HLO is identical within a homogeneous group, so cost is exactly
    affine in the group's layer count; two reduced-depth *unrolled* compiles
    recover the exact coefficients (three for zamba2, whose shared-block
    calls are a second count). Validated against full unrolled compiles in
    EXPERIMENTS.md §Dry-run.
    """
    T = cfg.n_layers
    if cfg.family in ("lstm", "conv1d"):
        return [(cfg, 1.0)]
    ids = cfg.hybrid_layer_ids
    if cfg.family == "hybrid" and len(ids) >= 2:
        # zamba2: f = a + n_calls·c_call + n_layers·b_layer (every layer has
        # a Mamba block; calls alternate blocks of one shape). Fit on
        # (ids[0]+1 layers, 1 call), (ids[0]+3, 1), (ids[1]+1, 2 calls).
        n = len(ids)
        la, lb, lc = ids[0] + 1, ids[0] + 3, ids[1] + 1
        w_c = n - 1.0
        w_b = (T - lc * w_c - la * (2.0 - n)) / (lb - la)
        w_a = 2.0 - n - w_b
        one = ids[:1]
        return [(cfg.with_(n_layers=la, hybrid_layer_ids=one), w_a),
                (cfg.with_(n_layers=lb, hybrid_layer_ids=one), w_b),
                (cfg.with_(n_layers=lc, hybrid_layer_ids=ids[:2]), w_c)]
    k = cfg.moe.first_dense if (cfg.family == "moe" and cfg.moe) else 0
    L1 = k + 1
    delta = min(6, T - L1)
    L2 = L1 + delta
    if T <= L2 or delta <= 0 or cfg.family == "hybrid":
        return [(cfg, 1.0)]
    w2 = (T - L1) / delta
    return [(cfg.with_(n_layers=L1), 1.0 - w2),
            (cfg.with_(n_layers=L2), w2)]


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               par: Optional[ParallelismConfig] = None, verbose: bool = True,
               mode: str = "extrapolate", cfg_transform=None):
    """Lower + compile one cell; returns (RooflineReport, compile_seconds).

    mode="unroll":      single full unrolled compile (exact, slow)
    mode="extrapolate": full-config compile with scan-over-layers (proves
                        lower/compile/sharding/memory at full scale) + 2-3
                        reduced-depth unrolled compiles whose affine
                        extrapolation gives exact flops/bytes/wire.
    """
    cfg = get_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    shape = shape_table_for(cfg)[shape_name]
    mcfg = mesh_config(multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    par = par or ParallelismConfig()
    mesh_name = "2x16x16" if multi_pod else "16x16"

    if mode == "unroll" or cfg.family in ("lstm", "conv1d"):
        cost, mem, hlo, dt = _compile_cell(cfg, shape, mcfg, mesh, par)
        rep = roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, n_devices=mesh.size,
            cost=cost, hlo_text=hlo,
            model_flops=model_flops_estimate(cfg, shape),
            memory_analysis=str(mem))
        rep_dt = dt
    elif mode == "proof":
        # full-scale scan compile only: proves lower/compile/sharding/memory
        # (used for the multi-pod pass; §Roofline reads the single-pod table)
        par_scan = dataclasses.replace(par, scan_layers=True)
        cost, mem, hlo, dt = _compile_cell(cfg, shape, mcfg, mesh, par_scan)
        rep = roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, n_devices=mesh.size,
            cost=cost, hlo_text=hlo,
            model_flops=model_flops_estimate(cfg, shape),
            memory_analysis=str(mem))
        rep_dt = dt
    else:
        # 1) full-scale proof: scan-over-layers compile
        par_scan = dataclasses.replace(par, scan_layers=True)
        _, mem, hlo_scan, dt_scan = _compile_cell(cfg, shape, mcfg, mesh,
                                                  par_scan)
        # 2) exact costs: reduced-depth unrolled compiles + affine combine
        flops = byts = 0.0
        from repro.energy.roofline import parse_collectives

        wire = 0.0
        coll_counts: dict = {}
        dts = [dt_scan]
        for cfg_L, w in extrapolation_plan(cfg):
            cost_L, _, hlo_L, dt_L = _compile_cell(cfg_L, shape, mcfg, mesh,
                                                   par)
            st_L = parse_collectives(hlo_L, mesh.size)
            flops += w * float(cost_L.get("flops", 0.0))
            byts += w * float(cost_L.get("bytes accessed", 0.0))
            wire += w * st_L.total_wire_bytes
            for k2, v in st_L.counts.items():
                coll_counts[k2] = coll_counts.get(k2, 0) + w * v
            dts.append(dt_L)
        rep = roofline(
            arch=arch, shape=shape_name, mesh=mesh_name, n_devices=mesh.size,
            cost={"flops": flops, "bytes accessed": byts}, hlo_text="",
            model_flops=model_flops_estimate(cfg, shape),
            memory_analysis=str(mem))
        # overwrite collective stats with the extrapolated ones
        rep.wire_bytes_per_device = wire
        rep.collective_s = wire / 50e9
        rep.collectives.counts = {k2: int(round(v))
                                  for k2, v in coll_counts.items()}
        terms = {"compute": rep.compute_s, "memory": rep.memory_s,
                 "collective": rep.collective_s}
        rep.bottleneck = max(terms, key=terms.get)
        rep.step_s = max(terms.values())
        rep.mfu = (rep.model_flops / (mesh.size * 197e12 * rep.step_s)
                   if rep.step_s > 0 else 0.0)
        rep_dt = sum(dts)

    if verbose:
        print(f"--- {arch} × {shape_name} × {mesh_name} "
              f"(compile {rep_dt:.1f}s, mode={mode}) ---")
        print(f"  memory_analysis: {rep.memory_analysis}")
        print(f"  flops/device={rep.flops_per_device:.3e} "
              f"bytes/device={rep.bytes_per_device:.3e} "
              f"wire/device={rep.wire_bytes_per_device:.3e}")
        print(f"  terms: compute={rep.compute_s*1e3:.2f}ms "
              f"memory={rep.memory_s*1e3:.2f}ms "
              f"collective={rep.collective_s*1e3:.2f}ms "
              f"-> bottleneck={rep.bottleneck} MFU={rep.mfu*100:.1f}%")
        print(f"  collectives: {rep.collectives.counts} "
              f"(in_while={rep.collectives.in_while})")
    return rep, rep_dt


def report_json(rep: RooflineReport, compile_s: float) -> dict:
    d = dataclasses.asdict(rep)
    d.pop("collectives", None)
    d["collective_counts"] = rep.collectives.counts
    d["collective_local_bytes"] = rep.collectives.local_bytes
    d["collective_wire_bytes"] = rep.collectives.wire_bytes
    d["collectives_in_while"] = rep.collectives.in_while
    d["compile_seconds"] = compile_s
    return d


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ALL_IDS))
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) for the chosen mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None, help="directory for per-cell JSON")
    ap.add_argument("--mode", default="extrapolate",
                    choices=["extrapolate", "unroll", "proof"],
                    help="extrapolate: full-scale scan compile + reduced-L "
                         "unrolled cost extrapolation; unroll: single exact "
                         "full unrolled compile (slow); proof: full-scale "
                         "scan compile only (multi-pod pass)")
    args = ap.parse_args(argv)

    par = ParallelismConfig()
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    cells = []
    if args.all:
        for arch in ALL_IDS:
            cfg = get_config(arch)
            for sh in shapes_for(cfg):
                cells.append((arch, sh))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    rows, failures = [], []
    for mp in meshes:
        for arch, sh in cells:
            try:
                rep, dt = lower_cell(arch, sh, multi_pod=mp, par=par,
                                     mode=args.mode)
                rows.append(rep)
                if args.json:
                    import pathlib

                    p = pathlib.Path(args.json)
                    p.mkdir(parents=True, exist_ok=True)
                    mesh_name = "2x16x16" if mp else "16x16"
                    (p / f"{arch}__{sh}__{mesh_name}.json").write_text(
                        json.dumps(report_json(rep, dt), indent=2))
            except Exception as e:  # noqa: BLE001 — report all failures at end
                failures.append((arch, sh, mp, repr(e)))
                print(f"FAILED {arch} × {sh} (multi_pod={mp}): {e}",
                      file=sys.stderr)

    print("\n" + HEADER)
    for r in rows:
        print(r.row())
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        return 1
    print(f"\nall {len(rows)} cells compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
