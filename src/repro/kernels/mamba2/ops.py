"""Public wrapper: (B,S,H,P) layout, group broadcast, optional h0 fold-in."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.mamba2.kernel import ssd_pallas


@partial(jax.jit, static_argnames=("chunk",))
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
        Cm: jax.Array, h0: Optional[jax.Array] = None, *, chunk: int = 128
        ) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); B/C: (B,S,G,N) with G=1.

    Returns (y (B,S,H,P), final_state (B,H,P,N)). Like the WKV6 template,
    a nonzero initial state is folded in post-hoc (the recurrence is linear
    in the state): y += (C e^{a_cs}) h0ᵀ and S += e^{a_tot} h0.
    """
    B, S, H, P = x.shape
    G = Bm.shape[2]
    if G != 1:
        raise NotImplementedError(
            f"the Pallas SSD template reads one B/C group, got G={G}; "
            "repro.model.ssm.ssd_chunked handles grouped B/C (the path "
            "mamba_apply takes)")
    xk = x.transpose(0, 2, 1, 3)                      # (B,H,S,P)
    y, hf = ssd_pallas(xk, dt.astype(jnp.float32), A.astype(jnp.float32),
                       Bm[:, :, 0], Cm[:, :, 0], chunk=chunk,
                       interpret=use_interpret())
    y = y.transpose(0, 2, 1, 3)
    if h0 is not None:
        a = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
        a_cs = jnp.cumsum(a, axis=1)                  # (B,S,H)
        cdec = Cm[:, :, 0].astype(jnp.float32)        # (B,S,N)
        y = y + jnp.einsum("bsn,bsh,bhpn->bshp", cdec, jnp.exp(a_cs),
                           h0).astype(y.dtype)
        hf = hf + h0 * jnp.exp(a_cs[:, -1])[..., None, None]  # (B,H,1,1)
    return y, hf
