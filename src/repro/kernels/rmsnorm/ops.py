"""Public wrapper for the fused RMSNorm kernel: arbitrary leading dims,
row padding; interpret mode on the CPU (``repro.kernels.interpret_mode``)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.rmsnorm import kernel as K


def rmsnorm(x, scale, eps: float = 1e-6, *, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    shape = x.shape
    d = shape[-1]
    rows = int(np.prod(shape[:-1]))
    xf = x.reshape(rows, d)
    pad = (-rows) % 8
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = K.rmsnorm_2d(xf, scale, eps=eps, interpret=interpret)
    if pad:
        out = out[:rows]
    return out.reshape(shape)
