"""Per-kernel shape/dtype sweeps: Pallas template (interpret=True on CPU)
vs the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import use_interpret
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.lstm_cell.ops import lstm_window
from repro.kernels.lstm_cell.ref import lstm_window_ref
from repro.kernels.lstm_cell_int import (CellSpec, lstm_window_int,
                                         lstm_window_int_pallas,
                                         lstm_window_int_ref)
from repro.kernels.mamba2.ops import ssd
from repro.kernels.quant_matmul.ops import quant_matmul
from repro.kernels.quant_matmul.ref import quant_matmul_ref, quantize_act
from repro.kernels.rwkv6.ops import wkv6
from repro.model.rwkv import wkv6_reference
from repro.model.ssm import ssd_reference
from repro.obs import capture
from repro.quant.fixedpoint import FxpFormat
from repro.quant.ptq import quantize_params_int8


# --------------------------------------------------------------------------
@pytest.mark.parametrize("mkn", [(128, 128, 128), (64, 200, 96),
                                 (256, 512, 384), (32, 96, 640)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul(mkn, dtype):
    M, K, N = mkn
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    ip = quantize_params_int8({"w": w})
    y_k = quant_matmul(x, ip.q["w"], ip.scale["w"])
    xq, xs = quantize_act(x)
    y_r = quant_matmul_ref(xq, ip.q["w"], xs, ip.scale["w"])
    assert float(jnp.max(jnp.abs(y_k - y_r))) < 1e-3
    rel = float(jnp.linalg.norm(y_k - x.astype(jnp.float32) @ w)
                / jnp.linalg.norm(x.astype(jnp.float32) @ w))
    assert rel < 0.03


# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(2, 256, 4, 64), (1, 512, 2, 128),
                                   (2, 256, 3, 96), (1, 384, 2, 160)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_fwd(shape, causal):
    B, S, H, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) * 0.5 for kk in ks)
    err = float(jnp.max(jnp.abs(flash_attention(q, k, v, causal)
                                - attention_ref(q, k, v, causal))))
    assert err < 2e-5, err


def test_flash_attention_grads():
    B, S, H, hd = 1, 256, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) * 0.5 for kk in ks)
    gk = jax.grad(lambda *a: jnp.sum(flash_attention(*a, True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(attention_ref(*a, True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_flash_attention_bf16():
    B, S, H, hd = 2, 256, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.bfloat16) * 0.5
               for kk in ks)
    o_k = flash_attention(q, k, v, True).astype(jnp.float32)
    o_r = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), True)
    assert float(jnp.max(jnp.abs(o_k - o_r))) < 0.03


# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(64, 6, 1, 20), (128, 6, 1, 20),
                                   (32, 12, 4, 32), (200, 6, 1, 20)])
def test_lstm_window(shape):
    B, S, din, hid = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (B, S, din))
    w = jax.random.normal(ks[1], (din + hid, 4 * hid)) * 0.3
    b = jax.random.normal(ks[2], (4 * hid,)) * 0.1
    err = float(jnp.max(jnp.abs(lstm_window(x, w, b)
                                - lstm_window_ref(x, w, b))))
    assert err < 1e-5, err


# --------------------------------------------------------------------------
def test_template_registry_matches_packages():
    """kernels.TEMPLATES lists exactly the template packages on disk, and
    each follows the kernel.py/ops.py/ref.py layout (ref optional)."""
    import importlib
    import pathlib

    import repro.kernels as K

    pkg_dir = pathlib.Path(K.__file__).parent
    on_disk = sorted(p.parent.name for p in pkg_dir.glob("*/kernel.py"))
    assert sorted(K.TEMPLATES) == on_disk
    for name in K.TEMPLATES:
        importlib.import_module(f"repro.kernels.{name}.kernel")
        importlib.import_module(f"repro.kernels.{name}.ops")


# --------------------------------------------------------------------------
@pytest.mark.parametrize("fmts", [
    (FxpFormat(8, 4), FxpFormat(8, 6), FxpFormat(16, 8)),   # the defaults
    (FxpFormat(9, 4), FxpFormat(12, 9), FxpFormat(16, 8)),  # §4 envelope edge
], ids=["act8-w8", "act9-w12"])
@pytest.mark.parametrize("shape", [(1, 6, 1, 20), (7, 6, 3, 16),
                                   (64, 4, 2, 8), (200, 6, 1, 20),
                                   # either side of the rows/lanes threshold
                                   (40, 6, 1, 20), (64, 6, 1, 20),
                                   # lanes across a padded 128-lane tile,
                                   # and a stacked cell (d_in = hidden)
                                   (300, 6, 1, 20), (130, 6, 20, 20)])
def test_lstm_window_int(shape, fmts):
    """Fused integer window vs the per-step oracle: EXACT int equality,
    in both layouts (``pick_schedule``)."""
    import numpy as np

    B, S, din, hid = shape
    A, W, C = fmts
    spec = CellSpec(seq_len=S, d_in=din, hidden=hid, act_fmt=A, state_fmt=C,
                    w_fmt=W, sig_lo=A.lo, tanh_lo=A.lo)
    rng = np.random.default_rng(B + S)
    x = jnp.asarray(rng.integers(A.lo, A.hi + 1, (B, S, din)), jnp.int32)
    w = jnp.asarray(rng.integers(W.lo, W.hi + 1, (din + hid, 4 * hid)),
                    jnp.int32)
    b = jnp.asarray(rng.integers(-(1 << 10), 1 << 10, (4 * hid,)), jnp.int32)
    # arbitrary in-range ROMs: exercises the gathers, not the activations
    depth = 2 ** A.total_bits
    sig = jnp.asarray(rng.integers(A.lo, A.hi + 1, depth), jnp.int32)
    tanh = jnp.asarray(rng.integers(A.lo, A.hi + 1, depth), jnp.int32)
    y_k = lstm_window_int(x, w, b, sig, tanh, spec=spec,
                          interpret=use_interpret())
    y_r = lstm_window_int_ref(x, w, b, sig, tanh, spec=spec)
    assert y_k.dtype == jnp.int32 and y_k.shape == (B, S, hid)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize("batch,schedule", [
    (1, "rows"), (40, "rows"), (41, "lanes"), (256, "lanes")])
def test_lstm_window_int_schedule_counter(batch, schedule):
    """Each trace of the fused kernel counts the layout it picked from the
    static batch size: rows up to 40 windows at hidden 20, lanes above."""
    A = FxpFormat(8, 4)
    spec = CellSpec(seq_len=6, d_in=1, hidden=20, act_fmt=A,
                    state_fmt=FxpFormat(16, 8), w_fmt=FxpFormat(8, 6),
                    sig_lo=A.lo, tanh_lo=A.lo)
    depth = 2 ** A.total_bits

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    with capture() as cap:
        jax.eval_shape(
            lambda *a: lstm_window_int_pallas(*a, spec=spec, block_b=128,
                                              interpret=True),
            i32(batch, 6, 1), i32(21, 80), i32(80), i32(depth), i32(depth))
    counts = {k: c.value for k, c in cap.metrics.counters.items()
              if k.startswith("rtl.kernels.lstm_window_int.")}
    assert counts == {f"rtl.kernels.lstm_window_int.schedule.{schedule}": 1}


# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(2, 64, 3, 16), (1, 128, 2, 32),
                                   (2, 32, 4, 16)])
@pytest.mark.parametrize("with_h0", [False, True])
def test_wkv6_kernel(shape, with_h0):
    B, S, H, N = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    r, k, v = (jax.random.normal(kk, shape) * 0.5 for kk in ks[:3])
    w_log = -jnp.exp(jax.random.normal(ks[3], shape) * 0.5)
    u = jax.random.normal(ks[4], (H, N)) * 0.5
    h0 = (jax.random.normal(ks[5], (B, H, N, N)) * 0.1) if with_h0 else None
    y_k, hf_k = wkv6(r, k, v, w_log, u, h0, chunk=32)
    y_r, hf_r = wkv6_reference(r, k, v, w_log, u, h0=h0)
    assert float(jnp.max(jnp.abs(y_k - y_r))) < 1e-4
    assert float(jnp.max(jnp.abs(hf_k - hf_r))) < 1e-4


# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(2, 64, 4, 16, 16), (1, 128, 2, 32, 16)])
@pytest.mark.parametrize("with_h0", [False, True])
def test_mamba2_kernel(shape, with_h0):
    B, S, H, P, N = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, 1, N)) * 0.5
    h0 = (jax.random.normal(ks[5], (B, H, P, N)) * 0.1) if with_h0 else None
    y_k, hf_k = ssd(x, dt, A, Bm, Cm, h0, chunk=16)
    y_r, hf_r = ssd_reference(x, dt, A, Bm, Cm, h0=h0)
    assert float(jnp.max(jnp.abs(y_k - y_r))) < 1e-4
    assert float(jnp.max(jnp.abs(hf_k - hf_r))) < 1e-4
