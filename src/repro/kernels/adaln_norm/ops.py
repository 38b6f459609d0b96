"""Public wrapper for the fused adaLN LayerNorm kernel: token-dim padding
to the sublane multiple; interpret mode on the CPU
(``repro.kernels.interpret_mode``)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.adaln_norm import kernel as K


def adaln_norm(x, scale, shift, eps: float = 1e-6, *,
               interpret: bool | None = None):
    """x: (B, N, d) tokens; scale/shift: (B, d) per-batch-row modulation."""
    if interpret is None:
        interpret = interpret_mode()
    B, N, d = x.shape
    pad = (-N) % 8
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    out = K.adaln_norm_3d(x, scale, shift, eps=eps, interpret=interpret)
    if pad:
        out = out[:, :N]
    return out
