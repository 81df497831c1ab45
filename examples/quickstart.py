"""Quickstart: the whole ElasticAI-JAX loop in one minute on CPU.

    PYTHONPATH=src python examples/quickstart.py

1. pick a registered architecture (reduced config),
2. train a few steps on the synthetic corpus,
3. "press the button": translate via the deployment-target registry ->
   (SynthesisReport, Deployment) — the report is the Vivado analogue, the
   Deployment the uniform deployable artifact (callable/measurable/savable),
4. serve a few batched requests from the trained weights.
"""
import jax

from repro.configs import get_config
from repro.core.creator import Creator
from repro.core.target import list_targets
from repro.core.types import SMOKE_MESH, ParallelismConfig, ShapeConfig
from repro.data.pipeline import LMDataConfig, lm_batch_for_step
from repro.runtime.server import Server, ServerConfig


def main():
    cfg = get_config("yi-9b", smoke=True)
    par = ParallelismConfig(compute_dtype="float32")
    creator = Creator()
    print("deployment targets registered:", list_targets())
    print("components used:", sorted(creator.validate(cfg)))

    # --- stage 1: design/train ------------------------------------------
    S, B = 64, 8
    st = creator.build(cfg, ShapeConfig("t", "train", S, B), SMOKE_MESH, par)
    params, opt = st.init()
    step = jax.jit(st.train_fn())
    dcfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=S, global_batch=B)
    for i in range(20):
        params, opt, m = step(params, opt, lm_batch_for_step(dcfg, i))
        if i % 5 == 0:
            print(f"step {i:3d} loss {float(m['loss']):.3f}")

    # --- stage 2: translate + estimation report ---------------------------
    syn, dep = creator.translate(st)
    print(f"\nSynthesisReport: fits={syn.fits} "
          f"est_latency={syn.est_latency_s*1e3:.2f} ms "
          f"bottleneck={syn.bottleneck}")
    print(f"Deployment: target={dep.target!r} "
          "(uniform artifact: callable / .measure / .save)")
    print("per-channel seconds:",
          {k: f"{v*1e6:.0f}us" for k, v in syn.channels.items()})

    # --- stage 3: deploy (serve) ------------------------------------------
    srv = Server(cfg, params, ServerConfig(batch_slots=2, max_len=96,
                                           eos_token=-1), SMOKE_MESH, par)
    for i in range(3):
        srv.submit(list(range(5 + i, 13 + i)), max_new_tokens=8)
    for r in srv.run_until_drained():
        print(f"req {r.rid} -> {r.out_tokens}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
