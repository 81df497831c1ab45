#!/usr/bin/env python3
"""Smoke run of the RTL emulator's main path on a TPU.

Drives Creator → RTL Deployment → verify → serving farm → design-space
exploration (DSE) once, through the entry points a user calls, at the
shipped widths of both designs (elastic-lstm, elastic-conv1d), and checks
every result integer for integer:

    python3 chip_smoke.py [--seed N]     # one chip: the four phases below
    python3 chip_smoke.py --chips 4      # sharded farm + sharded DSE only

One chip:

1. workflow     — ``Workflow.run_once`` for elastic-lstm as
                  ``examples/elastic_workflow.py --target rtl --verify``
                  runs it, with a few QAT steps: train, translate, measure,
                  verify;
2. deployments  — ``Creator.build`` → ``translate(target="rtl",
                  emulator_mode="fused")`` per design: the compiled program
                  holds the Pallas kernels, batches of 4096 and 1 window
                  equal the ``jnp`` path, the checked-in golden vectors
                  replay exactly, and ``verify`` passes on the ``rtl`` and
                  ``xla`` targets;
3. serving      — ``repro.serving.loadgen`` with mixed lstm/conv1d traffic;
                  no request fails or is dropped, and every response equals
                  the ``jnp`` path on its padded window;
4. dse          — ``MultiDesignEmulator`` over 32 elastic-lstm candidates in
                  one dispatch equals 32 sequential ``fused`` runs.

Four chips (``--chips 4``): the farm with ``ShardedExecutable`` members
against the unsharded farm, and K=32 sharded DSE against the unsharded
vmap, each on a mesh of 4 distinct devices.

Each phase prints one line of counts and timings. The last line of standard
output is ``{"ok": true, "device": {...}}``; a failing phase exits non-zero
without it. Without a TPU the script runs nothing and exits non-zero. Every
phase is a function taking its sizes, so it can be rehearsed on the CPU
(Pallas in interpret mode) at a small size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ARCHS = ("elastic-lstm", "elastic-conv1d")
SERVING_FAMILY = {"lstm": "elastic-lstm", "conv1d": "elastic-conv1d"}


class SmokeFailure(AssertionError):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def golden_vectors(arch: str):
    from repro.verify.vectors import load_vectors

    return load_vectors(os.path.join(HERE, "tests", "golden", "vectors",
                                     arch))


def flops(cfg) -> float:
    if cfg.family == "lstm":
        from repro.model.lstm import lstm_flops

        return float(lstm_flops(cfg))
    from repro.model.conv1d import conv1d_flops

    return float(conv1d_flops(cfg))


# --------------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------------- #


def phase_workflow(train_steps: int) -> str:
    """One trip round the paper's loop for elastic-lstm on the RTL target,
    built by the example script itself."""
    import importlib.util

    path = os.path.join(HERE, "examples", "elastic_workflow.py")
    spec = importlib.util.spec_from_file_location("elastic_workflow", path)
    demo = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(demo)
    demo.TRAIN_STEPS = train_steps
    wf, _ = demo.make_workflow("elastic-lstm", "rtl", verify=True)
    t0 = time.perf_counter()
    rec = wf.run_once(dict(demo.INITIAL_KNOBS))
    dt = time.perf_counter() - t0
    conf = rec.conformance
    check(conf is not None and conf.passed,
          f"workflow verify failed: {conf.to_json() if conf else None}")
    check(rec.measurement.target == "rtl", "stage 3 did not measure RTL")
    return (f"train_steps={train_steps} fmt={rec.design.weight_fmt} "
            f"eval_loss={rec.design.eval_loss:.5f} "
            f"cycles={rec.synthesis.resources['cycles']} "
            f"gop_per_j={rec.measurement.gop_per_j:.3f} "
            f"verify={conf.summary()!r} seconds={dt:.2f}")


def _check_kernels_compiled(dep, x_int) -> None:
    text = dep.emulator.lower(x_int).compile().as_text()
    check("tpu_custom_call" in text,
          f"{dep.graph.name}: compiled fused program holds no Pallas kernel")


def _verify_xla(arch: str) -> str:
    """``translate(target="xla")`` → ``verify`` against the float model.
    Both sides run at ``highest`` matmul precision, so the comparison is
    between f32 computations, not bf16 passes."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.creator import Creator
    from repro.core.types import shape_table_for, shapes_for

    cfg = get_config(arch)
    if cfg.family == "lstm":
        from repro.model.lstm import lstm_apply as apply_fn
    else:
        from repro.model.conv1d import conv1d_apply as apply_fn
    with jax.default_matmul_precision("highest"):
        cr = Creator()
        st = cr.build(cfg, shape_table_for(cfg)[shapes_for(cfg)[0]])
        _, dep = cr.translate(st, target="xla")
        params, _ = st.init()
        ab = st.abstract_inputs()
        batch = {k: (jax.random.normal(jax.random.PRNGKey(0), v.shape)
                     if k == "x" else jnp.zeros(v.shape, v.dtype))
                 for k, v in ab["batch"].items()}
        rep = dep.verify((params, batch), model=cfg.name,
                         model_flops=flops(cfg),
                         oracle=lambda p, b: apply_fn(p, b["x"], cfg))
    check(rep.passed, f"{arch}: xla verify failed: {rep.to_json()}")
    check(any("oracle agreement" in n for n in rep.notes),
          f"{arch}: xla verify ran no oracle comparison")
    return rep.protocol["platform"]


def phase_deployments(batches, seed: int, *, require_kernels: bool) -> str:
    """Per design: fused deployment through the Creator, checked against
    the jnp path, the golden vectors and both targets' ``verify``."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.creator import Creator
    from repro.core.types import shape_table_for, shapes_for
    from repro.energy.hw import XC7S15
    from repro.rtl import RTLEmulator, RTLOptions
    from repro.verify.vectors import canonical_graph

    rng = np.random.default_rng(seed)
    parts = []
    for arch in ARCHS:
        cfg = get_config(arch)
        cr = Creator(hw=XC7S15)
        st = cr.build(cfg, shape_table_for(cfg)[shapes_for(cfg)[0]])
        # the canonical weights the golden vectors were signed off with
        _, _, params = canonical_graph(arch)
        _, dep = cr.translate(st, target="rtl", params=params,
                              options=RTLOptions(emulator_mode="fused"),
                              model_flops=flops(cfg))
        ref = RTLEmulator(dep.graph, mode="jnp")
        in_edge = dep.graph.edges[dep.graph.inputs[0]]
        times = []
        for b in batches:
            x = rng.integers(in_edge.fmt.lo, in_edge.fmt.hi + 1,
                             size=(b,) + tuple(in_edge.shape)).astype(np.int32)
            if require_kernels:
                _check_kernels_compiled(dep, jax.numpy.asarray(x))
            t0 = time.perf_counter()
            got = np.asarray(dep.emulator.run_int(x).outputs)
            t1 = time.perf_counter()
            jax.block_until_ready(dep.emulator.run_int(x).outputs)
            t2 = time.perf_counter()
            want = np.asarray(ref.run_int(x).outputs)
            check(got.shape[0] == b and np.array_equal(got, want),
                  f"{arch} B={b}: fused != jnp at "
                  f"{int(np.sum(got != want))} positions")
            times.append(f"B={b}:first={t1 - t0:.3f}s,warm={t2 - t1:.5f}s")
        vs = golden_vectors(arch)
        got = np.asarray(dep.emulator.run_int(vs.stimulus).outputs)
        check(np.array_equal(got, vs.response),
              f"{arch}: golden vectors mismatch at "
              f"{int(np.sum(got != vs.response))} positions")
        rep = dep.verify(model=cfg.name, model_flops=flops(cfg))
        check(rep.passed, f"{arch}: rtl verify failed: {rep.to_json()}")
        xla_platform = _verify_xla(arch)
        parts.append(f"{arch}[{' '.join(times)} golden={vs.n_vectors}/"
                     f"{vs.n_vectors} rtl_verify=PASS xla_verify=PASS"
                     f"({xla_platform})]")
    return " ".join(parts)


def phase_serving(requests: int, max_batch: int, wave: int,
                  seed: int) -> str:
    """The loadgen CLI's farm on mixed traffic; every response re-derived
    on the jnp path from its padded window."""
    import numpy as np

    from repro.rtl import RTLEmulator
    from repro.serving import loadgen
    from repro.serving.batcher import pad_window
    from repro.serving.queue import DONE

    args = loadgen.parse_args([
        "--arch", "lstm,conv1d", "--requests", str(requests),
        "--max-batch", str(max_batch), "--wave", str(wave),
        "--seed", str(seed), "--warm"])
    t0 = time.perf_counter()
    report, farm, pools = loadgen.serve(args)
    dt = time.perf_counter() - t0
    fails = loadgen.failures(report, args)
    check(not fails, f"serving gate: {fails}")
    check(report["by_status"] == {"done": requests},
          f"serving statuses {report['by_status']}, want all done")
    stats = report["stats"]
    check(stats["redispatches"] == 0,
          f"{stats['redispatches']} dispatches failed and were retried")
    pool_of = {p.family: p for p in pools}
    groups = {}
    for req in farm.requests.values():
        check(req.status == DONE, f"request {req.rid} is {req.status}: "
                                  f"{req.error}")
        groups.setdefault((req.design, req.bucket_len), []).append(req)
    for (design, length), reqs in sorted(groups.items()):
        arr = np.stack([pad_window(np.asarray(r.window, np.float32), length)
                        for r in reqs])
        graph = pool_of[design].members[length][0].graph
        want = np.asarray(RTLEmulator(graph, mode="jnp").run(arr).outputs_f)
        got = np.stack([np.asarray(r.result) for r in reqs])
        check(np.array_equal(got, want),
              f"serving {design}/L={length}: responses differ from the jnp "
              f"path at {int(np.sum(got != want))} positions")
    return (f"requests={requests} done={report['by_status'].get('done', 0)} "
            f"failed=0 dropped={report['dropped_after_admission']} "
            f"dispatches={stats['dispatches']} "
            f"mean_batch={stats['batch_size'].get('mean', 0):.1f} "
            f"windows_per_s={report['throughput_windows_per_s']:.1f} "
            f"p50={report['latency_p50_s']:.5f}s "
            f"p99={report['latency_p99_s']:.5f}s "
            f"checked={sum(len(v) for v in groups.values())} "
            f"seconds={dt:.2f}")


def _dse_candidates(k: int, seed: int):
    """K isomorphic elastic-lstm candidates; #0 is the golden design."""
    from repro.verify.vectors import canonical_graph

    return [canonical_graph("elastic-lstm", seed=0)[0]] + [
        canonical_graph("elastic-lstm", seed=seed + 1 + i)[0]
        for i in range(k - 1)]


def phase_dse(k: int, seed: int) -> str:
    """K candidates in one vmapped dispatch vs K sequential fused runs."""
    import jax
    import numpy as np

    from repro.rtl import MultiDesignEmulator, RTLEmulator
    from repro.rtl.program_cache import ProgramLRU

    graphs = _dse_candidates(k, seed)
    vs = golden_vectors("elastic-lstm")
    t0 = time.perf_counter()
    multi = MultiDesignEmulator(graphs)
    out = np.asarray(jax.block_until_ready(
        multi.run_int(vs.stimulus).outputs))
    t1 = time.perf_counter()
    shared = ProgramLRU(2)
    seq = np.stack([np.asarray(RTLEmulator(g, mode="fused", programs=shared)
                               .run_int(vs.stimulus).outputs)
                    for g in graphs])
    t2 = time.perf_counter()
    check(out.shape[0] == k and np.array_equal(out, seq),
          f"DSE: vmapped != sequential fused at "
          f"{int(np.sum(out != seq))} positions")
    check(np.array_equal(out[0], vs.response),
          "DSE: candidate 0 does not reproduce the golden vectors")
    return (f"K={k} vectors={vs.n_vectors} dispatches=1 "
            f"vmapped={t1 - t0:.3f}s sequential_fused={t2 - t1:.3f}s "
            f"bit_exact=True")


# --------------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------------- #


def _distinct_devices(mesh, n: int) -> None:
    devs = list(mesh.devices.flat)
    check(len(devs) == n and len({d.id for d in devs}) == n,
          f"mesh holds {len(devs)} devices, "
          f"{len({d.id for d in devs})} distinct; want {n}")


def phase_sharded_farm(requests: int, max_batch: int, wave: int, seed: int,
                       n_devices: int) -> str:
    """The same tape through a farm of ``ShardedExecutable`` members and
    through the unsharded farm: every response integer-equal."""
    import numpy as np

    from repro.obs import MetricsRegistry
    from repro.serving import (AcceleratorFarm, DesignPool, FarmConfig,
                               ShardedExecutable, make_serving_mesh)
    from repro.serving.loadgen import TrafficSpec, build_farm, run_loadgen

    archs = ("lstm", "conv1d")
    cfg = FarmConfig(max_batch=max_batch)
    mesh = make_serving_mesh(n_devices)
    _distinct_devices(mesh, n_devices)
    plain, pools = build_farm(archs, replicas=1, cfg=cfg, seed=seed,
                              metrics=MetricsRegistry())
    sharded_pools = [DesignPool(
        family=p.family,
        members={ln: [ShardedExecutable(m, mesh) for m in reps]
                 for ln, reps in p.members.items()},
        flops_per_window=p.flops_per_window,
        energy_per_window_j=p.energy_per_window_j) for p in pools]
    sharded = AcceleratorFarm(sharded_pools, cfg, metrics=MetricsRegistry())
    spec = TrafficSpec(archs=archs, n_requests=requests, wave=wave,
                       seed=seed)
    t0 = time.perf_counter()
    rep_plain = run_loadgen(plain, pools, spec)
    t1 = time.perf_counter()
    rep_sharded = run_loadgen(sharded, sharded_pools, spec)
    t2 = time.perf_counter()
    for rep in (rep_plain, rep_sharded):
        check(rep["by_status"] == {"done": requests},
              f"farm statuses {rep['by_status']}, want all done")
    check(sorted(plain.requests) == sorted(sharded.requests),
          "the two farms saw different request ids")
    for rid, a in plain.requests.items():
        b = sharded.requests[rid]
        check(np.array_equal(np.asarray(a.result), np.asarray(b.result)),
              f"request {rid}: sharded response differs from unsharded")
    return (f"devices={n_devices} requests={requests} "
            f"dispatches={rep_sharded['stats']['dispatches']} "
            f"unsharded={t1 - t0:.2f}s sharded={t2 - t1:.2f}s "
            f"bit_exact=True")


def phase_sharded_dse(k: int, seed: int, n_devices: int) -> str:
    """K candidates with the design axis sharded over the mesh vs the
    unsharded vmap: integer-equal."""
    import jax
    import numpy as np

    from repro.rtl import MultiDesignEmulator

    graphs = _dse_candidates(k, seed)
    vs = golden_vectors("elastic-lstm")
    check(len(jax.devices()) == n_devices,
          f"{len(jax.devices())} devices present, want {n_devices}")
    sharded = MultiDesignEmulator(graphs, shard=True)
    _distinct_devices(sharded.mesh, n_devices)
    t0 = time.perf_counter()
    a = np.asarray(jax.block_until_ready(
        sharded.run_int(vs.stimulus).outputs))
    t1 = time.perf_counter()
    b = np.asarray(MultiDesignEmulator(graphs).run_int(vs.stimulus).outputs)
    t2 = time.perf_counter()
    check(np.array_equal(a, b), f"sharded DSE != unsharded vmap at "
                                f"{int(np.sum(a != b))} positions")
    check(np.array_equal(a[0], vs.response),
          "sharded DSE: candidate 0 does not reproduce the golden vectors")
    return (f"K={k} devices={n_devices} sharded={t1 - t0:.3f}s "
            f"unsharded={t2 - t1:.3f}s bit_exact=True")


# --------------------------------------------------------------------------- #
# driver
# --------------------------------------------------------------------------- #


def run_phase(name: str, fn, *args, **kw) -> bool:
    t0 = time.perf_counter()
    try:
        line = fn(*args, **kw)
    except Exception as e:  # noqa: BLE001 - reported, then the run fails
        traceback.print_exc()
        print(f"FAIL {name}: {type(e).__name__}: {e}"[:2000], flush=True)
        return False
    print(f"{name}: {line} phase_seconds={time.perf_counter() - t0:.2f}",
          flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded farm and sharded DSE "
                         "phases, on a mesh of 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}; "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.kernels import use_interpret
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if use_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode on "
              "the TPU", file=sys.stderr)
        return 1

    if args.chips == 4:
        ok = [run_phase("sharded_farm", phase_sharded_farm, 4096, 256, 1024,
                        args.seed, 4),
              run_phase("sharded_dse", phase_sharded_dse, 32, args.seed, 4)]
    else:
        ok = [run_phase("workflow", phase_workflow, 5),
              run_phase("deployments", phase_deployments, (4096, 1),
                        args.seed, require_kernels=True),
              run_phase("serving", phase_serving, 4096, 256, 1024,
                        args.seed),
              run_phase("dse", phase_dse, 32, args.seed)]
    if not all(ok):
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
