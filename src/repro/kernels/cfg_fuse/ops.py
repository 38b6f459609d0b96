"""jit'd public wrapper around the cfg_fuse Pallas kernel: handles
flattening/padding to the (rows, 128) lane layout; interpret mode on
the CPU (``repro.kernels.interpret_mode``)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.cfg_fuse import kernel as K


def cfg_update(x, eps_c, eps_u, s, ab_t, ab_prev, noise, eta: float = 1.0,
               *, interpret: bool | None = None):
    """Fused (1+s)·ε_c − s·ε_u guidance + ancestral update.  Shapes of
    x/eps_c/eps_u/noise are identical and arbitrary; s and eta are static."""
    if interpret is None:
        interpret = interpret_mode()
    shape = x.shape
    n = int(np.prod(shape))
    rows = -(-n // K.LANES)
    rows = -(-rows // 8) * 8
    pad = rows * K.LANES - n

    def flat(a):
        a = a.reshape(-1)
        if pad:
            a = jnp.pad(a, (0, pad))
        return a.reshape(rows, K.LANES)

    out = K.cfg_update_2d(flat(x), flat(eps_c), flat(eps_u), flat(noise),
                          jnp.asarray(ab_t, jnp.float32),
                          jnp.asarray(ab_prev, jnp.float32),
                          s=float(s), eta=float(eta), interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)


def cfg_update_rowwise(x, eps_c, eps_u, s, ab_t, ab_prev, noise, active,
                       eta: float = 1.0, *, row_offset: int = 0,
                       interpret: bool | None = None):
    """Per-row fused update for ragged waves: ``s``/``ab_t``/``ab_prev``/
    ``active`` are (Bs,) vectors — every batch row carries its own guidance
    scale and schedule position, and ``active`` freezes rows whose right-
    aligned trajectory has not started yet.  Each image is flattened to
    its own (rows, 128) lane block so the kernel's per-row scalars apply
    exactly to that image's elements.

    Row-window path: the scalar vectors may be WIDER than ``x``'s batch —
    tensor row b uses scalar slot ``row_offset + b`` — so a window of a
    wave's rows can update against the wave-wide scalar table without
    slicing a copy of it per step.  ``row_offset`` may be a traced scalar
    (the multi-host window path passes it as an operand so one compiled
    executable serves every host offset); the bounds check runs only for
    concrete offsets.  The in-tree compaction scheduler slices its
    segment tables host-side and always uses the default
    ``row_offset=0``."""
    if interpret is None:
        interpret = interpret_mode()
    shape = x.shape
    B = shape[0]
    n = int(np.prod(shape[1:]))
    rows = -(-n // K.LANES)
    rows = -(-rows // 8) * 8
    pad = rows * K.LANES - n

    def flat(a):
        a = a.reshape(B, -1)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)))
        return a.reshape(B, rows, K.LANES)

    scal = jnp.stack([
        jnp.asarray(ab_t, jnp.float32).reshape(-1),
        jnp.asarray(ab_prev, jnp.float32).reshape(-1),
        jnp.asarray(s, jnp.float32).reshape(-1),
        jnp.asarray(active).astype(jnp.float32).reshape(-1),
    ])
    if isinstance(row_offset, (int, np.integer)) and \
            (row_offset < 0 or scal.shape[1] < row_offset + B):
        raise ValueError(
            f"rowwise scalars span {scal.shape[1]} rows; window "
            f"[{row_offset}, {row_offset + B}) is out of range")
    off = jnp.asarray(row_offset, jnp.int32).reshape(1)
    out = K.cfg_update_rowwise_3d(flat(x), flat(eps_c), flat(eps_u),
                                  flat(noise), off, scal, eta=float(eta),
                                  interpret=interpret)
    return out.reshape(B, -1)[:, :n].reshape(shape)


def cfg_update_mixed(x, eps_c, eps_u, mode, s, ab_t, ab_prev, noise, active,
                     eta: float = 1.0, *, row_offset: int = 0,
                     interpret: bool | None = None):
    """Per-row MIXED-guidance fused update: like ``cfg_update_rowwise``
    but with a per-row ``mode`` selecting the guidance combine (0 = cfg
    pair-combine, uncond riding it as s=0 null-cond; 1 = ε_c is the
    classifier-corrected ε̂ computed upstream).  The scalar-prefetch
    table is (5, Bs) — ``(mode, ᾱ_t, ᾱ_prev, s, active)`` per row — and
    the same row-window contract applies: tensor row b reads scalar slot
    ``row_offset + b``, with the bounds check only for concrete offsets."""
    if interpret is None:
        interpret = interpret_mode()
    shape = x.shape
    B = shape[0]
    n = int(np.prod(shape[1:]))
    rows = -(-n // K.LANES)
    rows = -(-rows // 8) * 8
    pad = rows * K.LANES - n

    def flat(a):
        a = a.reshape(B, -1)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)))
        return a.reshape(B, rows, K.LANES)

    scal = jnp.stack([
        jnp.asarray(mode, jnp.float32).reshape(-1),
        jnp.asarray(ab_t, jnp.float32).reshape(-1),
        jnp.asarray(ab_prev, jnp.float32).reshape(-1),
        jnp.asarray(s, jnp.float32).reshape(-1),
        jnp.asarray(active).astype(jnp.float32).reshape(-1),
    ])
    if isinstance(row_offset, (int, np.integer)) and \
            (row_offset < 0 or scal.shape[1] < row_offset + B):
        raise ValueError(
            f"mixed scalars span {scal.shape[1]} rows; window "
            f"[{row_offset}, {row_offset + B}) is out of range")
    off = jnp.asarray(row_offset, jnp.int32).reshape(1)
    out = K.cfg_update_mixed_3d(flat(x), flat(eps_c), flat(eps_u),
                                flat(noise), off, scal, eta=float(eta),
                                interpret=interpret)
    return out.reshape(B, -1)[:, :n].reshape(shape)
