"""int8-ring gradient all-reduce: correctness vs psum + trainer integration."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# Every test here runs compressed_psum through shard_map. The subprocess
# bodies import ``repro.shardmap.shard_map``, the repo's one wrapper over
# ``jax.shard_map``.


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


def test_compressed_psum_matches_f32():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compress import compressed_psum_vec
        from repro.shardmap import shard_map

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        def both(x):
            return (jax.lax.psum(x, "data"),
                    compressed_psum_vec(x, "data"))
        f = shard_map(both, mesh=mesh, in_specs=P("data"),
                          out_specs=(P(), P()), axis_names={"data"}, check_vma=False)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))
        with mesh:
            exact, comp = jax.jit(f)(x.reshape(-1))
        rel = float(jnp.linalg.norm(comp - exact) / jnp.linalg.norm(exact))
        print("rel err:", rel)
        assert rel < 0.02, rel
    """)


def test_compressed_wire_bytes_less_than_f32():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compress import compressed_psum_vec
        from repro.shardmap import shard_map
        from repro.energy.roofline import parse_collectives

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        SZ = 1 << 16
        f32 = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                            in_specs=P("data"), out_specs=P(),
                            axis_names={"data"}, check_vma=False)
        cmp = shard_map(lambda x: compressed_psum_vec(x, "data"),
                            mesh=mesh, in_specs=P("data"), out_specs=P(),
                            axis_names={"data"}, check_vma=False)
        sds = jax.ShapeDtypeStruct((8 * SZ,), jnp.float32)
        with mesh:
            w_f32 = parse_collectives(
                jax.jit(f32).lower(sds).compile().as_text(), 8)
            w_cmp = parse_collectives(
                jax.jit(cmp).lower(sds).compile().as_text(), 8)
        print("f32 wire:", w_f32.total_wire_bytes,
              "int8 wire:", w_cmp.total_wire_bytes)
        assert w_cmp.total_wire_bytes < 0.45 * w_f32.total_wire_bytes
    """)


def test_trainer_with_compression_learns():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core.types import MeshConfig, ParallelismConfig, ShapeConfig
        from repro.data.pipeline import LMDataConfig, lm_batch_for_step
        from repro.model.lm import Stepper

        cfg = get_config("yi-9b", smoke=True)
        mcfg = MeshConfig((4, 2), ("data", "model"))
        mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        par = ParallelismConfig(compute_dtype="float32",
                                grad_compression=True)
        st = Stepper(cfg, ShapeConfig("t", "train", 32, 8), mcfg, par,
                     mesh=mesh)
        params, opt = st.init()
        dcfg = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                            global_batch=8)
        with mesh:
            step = jax.jit(st.train_fn())
            losses = []
            # overfit one fixed batch: fresh random batches carry no
            # learnable signal in 15 steps, so the integration check is
            # "grads flow through the compressed reduction and the loss
            # memorizes", the standard trainer smoke
            batch = lm_batch_for_step(dcfg, 0)
            for i in range(15):
                params, opt, m = step(params, opt, batch)
                losses.append(float(m["loss"]))
        print("losses:", losses[0], "->", losses[-1])
        assert losses[-1] < losses[0] - 0.1, losses
    """)
