"""Pallas TPU fused adaLN LayerNorm: one VMEM pass computing the DiT's
mean-subtracting LayerNorm plus the adaLN-zero modulation
``(1 + scale)·x̂ + shift`` — replacing the naive mean/var/normalise/
mul/add HBM round-trips at each of the three DiT modulation sites.

Tiling: grid (B, token blocks); each program holds a (block_n, d) slab of
one batch row's tokens with that row's (d,) scale/shift resident — d stays
whole so the row reduction is VMEM-local.  Sibling of ``kernels/rmsnorm``
with per-batch-row modulation operands instead of one shared gain."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_TOKENS = 256


def _adaln_kernel(x_ref, s_ref, b_ref, o_ref, *, eps):
    x = x_ref[0].astype(jnp.float32)                    # (block_n, d)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    s = s_ref[0].astype(jnp.float32)                    # (1, d)
    b = b_ref[0].astype(jnp.float32)
    o_ref[0] = (y * (1.0 + s) + b).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def adaln_norm_3d(x, scale, shift, *, eps: float = 1e-6,
                  interpret: bool = False):
    """x: (B, N, d); scale/shift: (B, d).  The modulation rows go in as
    (B, 1, d) with a (1, 1, d) block: Mosaic needs a block's last two
    dims to be (8, 128)-divisible or equal to the array's, and a (1, d)
    block of a (B, d) array is neither."""
    B, N, d = x.shape
    block = min(BLOCK_TOKENS, N)
    return pl.pallas_call(
        functools.partial(_adaln_kernel, eps=eps),
        grid=(B, pl.cdiv(N, block)),
        in_specs=[pl.BlockSpec((1, block, d), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, 1, d), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, 1, d), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, block, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="adaln_norm",
    )(x, scale[:, None, :], shift[:, None, :])
