"""End-to-end driver: train a ~100M-param LM on the synthetic corpus with the
fault-tolerant trainer (checkpoint/restart + deterministic replay).

    PYTHONPATH=src python examples/train_lm.py --preset 25m --steps 200
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is the brief's "~100M model for a few hundred steps"; 25m
finishes in minutes on the container CPU (same code path).
"""
import argparse

from repro.core.types import ModelConfig, ParallelismConfig, ShapeConfig, \
    SMOKE_MESH
from repro.data.pipeline import LMDataConfig
from repro.model.lm import Stepper
from repro.optim.adamw import AdamWConfig
from repro.runtime.failures import FailureInjector
from repro.runtime.trainer import Trainer, TrainerConfig

PRESETS = {
    # ~26M params: d=512, 8L, v=8192
    "25m": dict(n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
                d_ff=1408, vocab_size=8192, seq=256, batch=8),
    # ~101M params: d=768, 12L, v=32768
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 d_ff=2048, vocab_size=32768, seq=512, batch=8),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="25m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure", type=int, default=-1,
                    help="step at which to inject a preemption (demo)")
    args = ap.parse_args()

    p = PRESETS[args.preset]
    cfg = ModelConfig(
        name=f"lm-{args.preset}", family="dense", n_layers=p["n_layers"],
        d_model=p["d_model"], n_heads=p["n_heads"],
        n_kv_heads=p["n_kv_heads"], d_ff=p["d_ff"],
        vocab_size=p["vocab_size"], vocab_pad_multiple=128, act="silu",
        norm="rmsnorm", remat="full")
    par = ParallelismConfig(compute_dtype="float32")
    st = Stepper(cfg, ShapeConfig("t", "train", p["seq"], p["batch"]),
                 SMOKE_MESH, par,
                 opt_cfg=AdamWConfig(lr=6e-4, warmup_steps=20,
                                     total_steps=args.steps))
    n_params = sum(x.size for x in __import__("jax").tree.leaves(st.init()[0]))
    print(f"model: {n_params/1e6:.1f}M params, seq={p['seq']}, "
          f"batch={p['batch']}")

    inj = None
    if args.inject_failure >= 0:
        inj = FailureInjector(fail_at_steps={args.inject_failure})
    dcfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=p["seq"],
                        global_batch=p["batch"])
    tr = Trainer(st, dcfg,
                 TrainerConfig(total_steps=args.steps, ckpt_every=50,
                               ckpt_dir=args.ckpt_dir, log_every=10),
                 injector=inj)
    out = tr.train()
    first, last = out["metrics"][0], out["metrics"][-1]
    print(f"\nloss {first['loss']:.3f} (step {first['step']}) -> "
          f"{last['loss']:.3f} (step {last['step']}); "
          f"recoveries={out['recoveries']}")
    assert last["loss"] < first["loss"], "no learning happened?!"


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
