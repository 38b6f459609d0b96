"""Pallas TPU kernel: fused CFG guidance-combine + ancestral update.

On GPU implementations (diffusers etc.) this is a chain of ~10 elementwise
HBM round-trips; here it is ONE VMEM-resident pass over (x, ε_c, ε_u, z).
Tiling: inputs flattened to (rows, 128) lanes, 8-row sublane alignment,
(256, 128) VMEM blocks.  The per-step schedule constants (ᾱ_t, ᾱ_prev) are
traced scalars carried in SMEM; the guidance scale s and η are static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 256


def _cfg_kernel(scal_ref, x_ref, ec_ref, eu_ref, z_ref, out_ref, *, s, eta):
    ab_t = scal_ref[0]
    ab_prev = scal_ref[1]
    x = x_ref[...].astype(jnp.float32)
    eps = (1.0 + s) * ec_ref[...].astype(jnp.float32) \
        - s * eu_ref[...].astype(jnp.float32)
    x0 = (x - jnp.sqrt(1.0 - ab_t) * eps) * jax.lax.rsqrt(ab_t)
    x0 = jnp.clip(x0, -1.0, 1.0)
    var = (1.0 - ab_prev) / (1.0 - ab_t) * (1.0 - ab_t / ab_prev)
    sigma = eta * jnp.sqrt(jnp.maximum(var, 0.0))
    dir_coef = jnp.sqrt(jnp.maximum(1.0 - ab_prev - sigma * sigma, 0.0))
    out = jnp.sqrt(ab_prev) * x0 + dir_coef * eps \
        + sigma * z_ref[...].astype(jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


def _cfg_rowwise_kernel(off_ref, scal_ref, x_ref, ec_ref, eu_ref, z_ref,
                        out_ref, *, eta):
    # segment-offset indexing: tensor row b reads its scalars at column
    # off + b of a scalar table that may span a WIDER row range than this
    # launch — a compaction segment (or a per-host window of a sharded
    # wave) addresses its window of the wave-resident (4, B_wave) table
    # instead of materialising a sliced copy per segment per step.
    b = off_ref[0] + pl.program_id(0)
    ab_t = scal_ref[0, b]
    ab_prev = scal_ref[1, b]
    s = scal_ref[2, b]
    act = scal_ref[3, b]
    x = x_ref[...].astype(jnp.float32)
    eps = (1.0 + s) * ec_ref[...].astype(jnp.float32) \
        - s * eu_ref[...].astype(jnp.float32)
    x0 = (x - jnp.sqrt(1.0 - ab_t) * eps) * jax.lax.rsqrt(ab_t)
    x0 = jnp.clip(x0, -1.0, 1.0)
    var = (1.0 - ab_prev) / (1.0 - ab_t) * (1.0 - ab_t / ab_prev)
    sigma = eta * jnp.sqrt(jnp.maximum(var, 0.0))
    dir_coef = jnp.sqrt(jnp.maximum(1.0 - ab_prev - sigma * sigma, 0.0))
    out = jnp.sqrt(ab_prev) * x0 + dir_coef * eps \
        + sigma * z_ref[...].astype(jnp.float32)
    out = jnp.where(act > 0.0, out, x)
    out_ref[...] = out.astype(out_ref.dtype)


def _cfg_mixed_kernel(off_ref, scal_ref, x_ref, ec_ref, eu_ref, z_ref,
                      out_ref, *, eta):
    # mixed-guidance row: the (5, Bs) scalar table carries one
    # (mode, ᾱ_t, ᾱ_prev, s, active) tuple per wave row.  mode selects
    # the guidance combine — 0 is the cfg pair-combine (uncond rides it
    # as s=0 with a null cond row), 1 takes ε_c as the classifier-
    # corrected ε̂ computed upstream.  Same segment-offset indexing as
    # the pure-cfg rowwise kernel: tensor row b reads column off + b.
    b = off_ref[0] + pl.program_id(0)
    mode = scal_ref[0, b]
    ab_t = scal_ref[1, b]
    ab_prev = scal_ref[2, b]
    s = scal_ref[3, b]
    act = scal_ref[4, b]
    x = x_ref[...].astype(jnp.float32)
    ec = ec_ref[...].astype(jnp.float32)
    eu = eu_ref[...].astype(jnp.float32)
    eps = jnp.where(mode < 0.5, (1.0 + s) * ec - s * eu, ec)
    x0 = (x - jnp.sqrt(1.0 - ab_t) * eps) * jax.lax.rsqrt(ab_t)
    x0 = jnp.clip(x0, -1.0, 1.0)
    var = (1.0 - ab_prev) / (1.0 - ab_t) * (1.0 - ab_t / ab_prev)
    sigma = eta * jnp.sqrt(jnp.maximum(var, 0.0))
    dir_coef = jnp.sqrt(jnp.maximum(1.0 - ab_prev - sigma * sigma, 0.0))
    out = jnp.sqrt(ab_prev) * x0 + dir_coef * eps \
        + sigma * z_ref[...].astype(jnp.float32)
    out = jnp.where(act > 0.0, out, x)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eta", "interpret"))
def cfg_update_mixed_3d(x, eps_c, eps_u, noise, off, scal, *,
                        eta: float = 1.0, interpret: bool = False):
    """Mixed-guidance sibling of ``cfg_update_rowwise_3d``: identical
    grid/layout, but the scalar-prefetch table is (5, Bs) — a per-row
    ``(mode, ᾱ_t, ᾱ_prev, s, active)`` tuple — so cfg, classifier-guided
    and uncond rows share one launch (and one compiled executable)."""
    B, R, _ = x.shape
    block = min(BLOCK_ROWS, R)
    grid = (B, pl.cdiv(R, block))
    kern = functools.partial(_cfg_mixed_kernel, eta=float(eta))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec((1, block, LANES),
                                   lambda b, j, o, s: (b, j, 0))] * 4,
            out_specs=pl.BlockSpec((1, block, LANES),
                                   lambda b, j, o, s: (b, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="cfg_fuse_mixed",
    )(off, scal, x, eps_c, eps_u, noise)


@functools.partial(jax.jit, static_argnames=("eta", "interpret"))
def cfg_update_rowwise_3d(x, eps_c, eps_u, noise, off, scal, *,
                          eta: float = 1.0, interpret: bool = False):
    """Ragged-wave variant: one grid row per batch element, so every row
    reads its OWN (ᾱ_t, ᾱ_prev, s, active) from the (4, Bs) scalar-prefetch
    array — rows from different (guidance, steps) groups share one kernel
    launch.  Tensor args are pre-laid-out (B, R, 128), R % 8 == 0; a row
    whose ``active`` slot is 0 passes through bit-unchanged.

    ``off`` ((1,) int32 prefetch) is the row-window offset: tensor row b
    reads scalar column ``off + b``, so ``scal`` may carry a whole wave's
    per-row scalars (Bs >= off + B) while this launch updates only a
    window of its rows.  Forward-looking substrate (ROADMAP multi-host):
    today's compaction segments slice their tables host-side up front and
    always call with ``off == 0``; a per-host window of a wave-resident
    table is what needs a non-zero offset."""
    B, R, _ = x.shape
    block = min(BLOCK_ROWS, R)
    grid = (B, pl.cdiv(R, block))
    kern = functools.partial(_cfg_rowwise_kernel, eta=float(eta))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec((1, block, LANES),
                                   lambda b, j, o, s: (b, j, 0))] * 4,
            out_specs=pl.BlockSpec((1, block, LANES),
                                   lambda b, j, o, s: (b, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="cfg_fuse_rowwise",
    )(off, scal, x, eps_c, eps_u, noise)


@functools.partial(jax.jit, static_argnames=("s", "eta", "interpret"))
def cfg_update_2d(x, eps_c, eps_u, noise, ab_t, ab_prev, *, s: float,
                  eta: float = 1.0, interpret: bool = False):
    """All tensor args pre-flattened to (rows, 128), rows % 8 == 0."""
    rows = x.shape[0]
    block = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block),)
    scal = jnp.stack([ab_t, ab_prev]).astype(jnp.float32)
    kern = functools.partial(_cfg_kernel, s=float(s), eta=float(eta))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((block, LANES), lambda i, s: (i, 0))] * 4,
            out_specs=pl.BlockSpec((block, LANES), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        name="cfg_fuse",
    )(scal, x, eps_c, eps_u, noise)
