"""RTL-template vs HLS analogue: Pallas kernel templates vs plain-XLA lowering.

The paper's motivation for hand-written RTL templates is Blott et al.'s 45 %
HLS resource overhead. The TPU analogue: for each hot component, compare the
plain-XLA lowering ("HLS") against the kernel template ("RTL") on:
  * HBM bytes per call (from compiled cost_analysis vs the template's
    streaming-traffic model),
  * estimated TPU v5e time (roofline max of compute/memory terms),
  * container wall-clock of the two numerics (f32 XLA vs int8 path).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.energy.hw import TPU_V5E
from repro.energy.roofline import normalize_cost


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    ca = normalize_cost(c.cost_analysis())
    return float(ca.get("flops", 0)), float(ca.get("bytes accessed", 0)), c


def _walltime(fn, args, n=5):
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def bench_attention(B=4, S=2048, H=8, hd=128):
    from repro.kernels.flash_attention.ref import attention_ref

    sds = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16)
    flops, byts, _ = _cost(lambda q, k, v: attention_ref(q, k, v, True),
                           sds, sds, sds)
    # template streaming model: Q,K,V read once + O written once (+ the
    # (bq,Sk) f32 running blocks stay in VMEM)
    t_bytes = 4 * (B * S * H * hd * 2)
    t_flops = flops  # identical math
    est = lambda f, b: max(f / TPU_V5E.peak_flops, b / TPU_V5E.hbm_bw)
    print(f"flash_attention  B{B} S{S} H{H} hd{hd}:")
    print(f"  XLA(HLS-analogue): bytes={byts:.3e}  est={est(flops, byts)*1e6:8.1f} us")
    print(f"  template(RTL):     bytes={t_bytes:.3e}  "
          f"est={est(t_flops, t_bytes)*1e6:8.1f} us"
          f"   traffic x{byts/t_bytes:.1f} less")
    return {"xla_bytes": byts, "tpl_bytes": t_bytes,
            "speedup_est": est(flops, byts) / est(t_flops, t_bytes)}


def bench_quant_matmul(M=512, K=4096, N=4096):
    from repro.quant.ptq import quantize_params_int8

    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    ip = quantize_params_int8({"w": w})
    flops, byts, _ = _cost(lambda a, b: a @ b, x, w)
    # int8 path: weights 1 B/elem, activations quantized once
    t_bytes = M * K * 1 + K * N * 1 + M * N * 4 + M * K * 4
    # int8 MXU runs ~2x bf16 rate on TPU; keep the brief's single constant
    est = lambda f, b, pk: max(f / pk, b / TPU_V5E.hbm_bw)
    t_xla = est(flops, byts, TPU_V5E.peak_flops)
    t_tpl = est(flops, t_bytes, 2 * TPU_V5E.peak_flops)
    wt_f32 = _walltime(lambda a, b: a @ b, (x, w))
    from repro.kernels.quant_matmul.ref import quant_matmul_ref, quantize_act

    xq, xs = quantize_act(x)
    wt_int8 = _walltime(
        lambda a, b: quant_matmul_ref(a, b, xs, ip.scale["w"]),
        (xq, ip.q["w"]))
    print(f"quant_matmul M{M} K{K} N{N}:")
    print(f"  XLA f32:  bytes={byts:.3e}  est={t_xla*1e6:8.1f} us  "
          f"wall={wt_f32*1e6:8.0f} us")
    print(f"  int8 tpl: bytes={t_bytes:.3e}  est={t_tpl*1e6:8.1f} us  "
          f"wall={wt_int8*1e6:8.0f} us"
          "   weight-bytes x4 less")
    return {"est_speedup": t_xla / t_tpl, "wall_f32": wt_f32,
            "wall_int8": wt_int8}


def bench_wkv(B=2, S=1024, H=8, N=64):
    from repro.model.rwkv import wkv6_chunked, wkv6_reference

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r, k, v = (jax.random.normal(kk, (B, S, H, N)) * 0.5 for kk in ks[:3])
    w_log = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N)) * 0.5)
    u = jax.random.normal(ks[4], (H, N)) * 0.5
    wt_scan = _walltime(lambda *a: wkv6_reference(*a)[0], (r, k, v, w_log, u),
                        n=3)
    wt_chunk = _walltime(
        lambda *a: wkv6_chunked(*a, chunk=128)[0], (r, k, v, w_log, u), n=3)
    print(f"wkv6 B{B} S{S} H{H} N{N}: scan={wt_scan*1e3:.1f} ms  "
          f"chunked={wt_chunk*1e3:.1f} ms  x{wt_scan/wt_chunk:.1f}")
    return {"scan_ms": wt_scan * 1e3, "chunked_ms": wt_chunk * 1e3,
            "speedup": wt_scan / wt_chunk}


def run() -> dict:
    out = {}
    out["attention"] = bench_attention()
    out["quant_matmul"] = bench_quant_matmul()
    out["wkv"] = bench_wkv()
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    run()
