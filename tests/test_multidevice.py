"""Multi-device behaviour — run in subprocesses with 8 forced host devices
(the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# The MoE EP paths go through ``repro.shardmap.shard_map``, the repo's one
# wrapper over ``jax.shard_map``.


def run_sub(body: str, n_dev: int = 8, timeout: int = 900) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_dev}")
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys
        sys.path.insert(0, {ROOT + "/src"!r})
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_moe_ep_impls_match_dense_oracle():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core.types import MeshConfig, ParallelismConfig
        from repro.model.layers import Ctx, init_params
        from repro.model.moe import moe_schema, moe_dense, moe_psum, moe_a2a

        cfg = get_config("qwen3-moe-30b-a3b", smoke=True)
        # 8 experts over tp=4 -> 2 local experts/shard
        mcfg = MeshConfig((2, 4), ("data", "model"))
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        par = ParallelismConfig(compute_dtype="float32")
        schema = moe_schema(cfg, tp=4)
        params = init_params(schema, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        # capacity high enough that no token drops -> exact match possible
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        with mesh:
            ctx = Ctx(cfg=cfg, mesh_cfg=mcfg, mode="train", mesh=mesh, par=par)
            y_d, aux_d = moe_dense(params, x, cfg, ctx)
            y_p, aux_p = moe_psum(params, x, cfg, ctx)
            y_a, aux_a = moe_a2a(params, x, cfg, ctx)
        err_p = float(jnp.max(jnp.abs(y_p - y_d)))
        err_a = float(jnp.max(jnp.abs(y_a - y_d)))
        print("psum err", err_p, "a2a err", err_a)
        assert err_p < 2e-4, err_p
        assert err_a < 2e-4, err_a
        # aux: per-DP-shard load-balance stats vs global stats are different
        # (equally valid) estimators — same scale, not bit-equal
        rel = abs(float(aux_p - aux_d)) / max(abs(float(aux_d)), 1e-9)
        assert rel < 0.5, (float(aux_p), float(aux_d))
    """)


def test_elastic_restart_reshards():
    run_sub("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.core.types import MeshConfig, ParallelismConfig, ShapeConfig
        from repro.data.pipeline import LMDataConfig
        from repro.model.lm import Stepper
        from repro.runtime.trainer import Trainer, TrainerConfig

        cfg = get_config("yi-9b", smoke=True)
        par = ParallelismConfig(compute_dtype="float32")
        S, B = 16, 8
        dcfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=S,
                            global_batch=B)
        td = tempfile.mkdtemp()

        # train 12 steps on a (4 dp, 2 tp) mesh
        mcfg1 = MeshConfig((4, 2), ("data", "model"))
        mesh1 = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                     ("data", "model"))
        st1 = Stepper(cfg, ShapeConfig("t", "train", S, B), mcfg1, par,
                      mesh=mesh1)
        tr1 = Trainer(st1, dcfg, TrainerConfig(total_steps=12, ckpt_every=5,
                                               ckpt_dir=td, log_every=5))
        with mesh1:
            out1 = tr1.train()

        # elastic restart: same checkpoint, (2 dp, 4 tp) mesh
        mcfg2 = MeshConfig((2, 4), ("data", "model"))
        mesh2 = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                     ("data", "model"))
        st2 = Stepper(cfg, ShapeConfig("t", "train", S, B), mcfg2, par,
                      mesh=mesh2)
        shard2 = {"params": st2.shardings(st2.schema), "opt": None}
        step, state = tr1.resume_elastic(st2)
        print("resumed at", step)
        assert step == 11
        # continue training on the new mesh
        with mesh2:
            fn = jax.jit(st2.train_fn())
            from repro.data.pipeline import lm_batch_for_step
            p, o, m = fn(state["params"], state["opt"],
                         lm_batch_for_step(dcfg, step))
        assert jnp.isfinite(m["loss"])
        print("elastic OK, loss", float(m["loss"]))
    """)


def test_dryrun_minimal_mesh_compiles():
    """A miniature production mesh (2x4) exercises the full dry-run path
    (shardings, donation, roofline) quickly."""
    run_sub("""
        import numpy as np, jax
        jax.devices()   # lock device count BEFORE dryrun's XLA_FLAGS line
        from jax.sharding import Mesh
        import repro.launch.dryrun as dr
        from repro.configs import get_config
        from repro.core.types import (MeshConfig, ParallelismConfig, SHAPES,
                                      ShapeConfig)

        cfg = get_config("internvl2-1b")
        cfg = cfg.with_(n_layers=2)
        shape = ShapeConfig("t", "train", 512, 8)
        mcfg = MeshConfig((2, 4), ("data", "model"))
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        par = ParallelismConfig()
        cost, mem, hlo, dt = dr._compile_cell(cfg, shape, mcfg, mesh, par)
        assert cost.get("flops", 0) > 0
        from repro.energy.roofline import parse_collectives
        stc = parse_collectives(hlo, 8)
        print("collectives:", stc.counts, "wire:", stc.total_wire_bytes)
        assert stc.total_wire_bytes > 0
    """)
