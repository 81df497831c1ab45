"""End-to-end serving driver (the paper's kind is deployment/inference):
train briefly, then serve a stream of batched requests with continuous
batching, reporting throughput and per-request latency.

    PYTHONPATH=src python examples/serve_lm.py --requests 12 --slots 4
"""
import argparse
import time

import jax

from repro.configs import get_config
from repro.core.types import SMOKE_MESH, ParallelismConfig, ShapeConfig
from repro.data.pipeline import LMDataConfig, lm_batch_for_step
from repro.model.lm import Stepper
from repro.runtime.server import Server, ServerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--train-steps", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    par = ParallelismConfig(compute_dtype="float32")
    S, B = 64, 8
    st = Stepper(cfg, ShapeConfig("t", "train", S, B), SMOKE_MESH, par)
    params, opt = st.init()
    step = jax.jit(st.train_fn())
    dcfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=S, global_batch=B)
    for i in range(args.train_steps):
        params, opt, m = step(params, opt, lm_batch_for_step(dcfg, i))
    print(f"warm model after {args.train_steps} steps: "
          f"loss {float(m['loss']):.3f}")

    srv = Server(cfg, params,
                 ServerConfig(batch_slots=args.slots, max_len=128,
                              eos_token=-1), SMOKE_MESH, par)
    t_submit = {}
    t0 = time.perf_counter()
    for i in range(args.requests):
        rid = srv.submit(list(range(3 + i, 20 + i)),
                         max_new_tokens=args.max_new)
        t_submit[rid] = time.perf_counter()
    reqs = srv.run_until_drained()
    dt = time.perf_counter() - t0
    tok = sum(len(r.out_tokens) for r in reqs)
    print(f"{len(reqs)} requests x {args.max_new} tokens in {dt:.2f}s -> "
          f"{tok/dt:.1f} tok/s with {args.slots} slots")
    for r in reqs[:4]:
        print(f"  req {r.rid}: {r.out_tokens[:8]}...")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
