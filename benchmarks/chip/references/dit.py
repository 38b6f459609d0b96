"""Plain reference of the served rows: the DiT denoiser and its guided
ancestral sampler in straightforward ``jax.numpy``.

It follows the published DiT block (Peebles & Xie, arXiv:2212.09748:
patchify, adaLN-zero blocks with six modulation vectors, tanh-GELU MLP of
ratio 4, LayerNorm without affine at eps 1e-6, final adaLN layer,
unpatchify) as the system under test lays it out, with its departures
from the paper noted in each configuration's ``assumed``: a conditioning
token prepended to the patch tokens, a 512-d encoding projected into the
adaLN conditioning in place of the class-embedding table, a sinusoidal
timestep embedding of width d, and no learned variance.  The sampler is
classifier-free guidance, ε̂ = (1+s)·ε(x, t, y) − s·ε(x, t, Ø) (Ho &
Salimans), inside DDPM ancestral steps (η = 1) on a cosine schedule
respaced to the row's own step count, with x̂₀ clipped to [−1, 1].  An
unconditional row is the s = 0 case with y = Ø.

Row noise follows the serving contract the rows are compared under: row
``i`` of request ``rid`` in a drain keyed ``k`` draws its x_T from
``fold_in(r, 0)`` and its step-``j`` noise from ``fold_in(r, 1 + j)``,
with ``r = fold_in(fold_in(k, rid), i)``.

Nothing here imports the system under test.  At ``precision=HIGHEST``
it is the reference.  With ``STATED`` it is the same reference at the
precision the configurations state (float32 parameters and activations,
matrix products at the default precision, which a TPU runs as one
bfloat16 pass with float32 accumulation): that says how far the stated
precision alone moves each row.  With ``CONTROL`` the whole denoiser runs
in bfloat16 (parameters, activations and the elementwise work): the
control, one precision below the stated float32, that a limit has to
reject.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STATED = dict(dtype=jnp.float32, precision=jax.lax.Precision.DEFAULT)
CONTROL = dict(dtype=jnp.bfloat16, precision=jax.lax.Precision.DEFAULT)


def cosine_alpha_bar(T: int) -> jnp.ndarray:
    """ᾱ_t of Nichol & Dhariwal's cosine schedule, float32, t = 0..T-1."""
    s = 0.008
    t = jnp.arange(T + 1) / T
    f = jnp.cos((t + s) / (1 + s) * math.pi / 2) ** 2
    ab = f / f[0]
    betas = jnp.clip(1 - ab[1:] / ab[:-1], 0, 0.999)
    return jnp.cumprod(1.0 - betas)


def respaced(T: int, k: int) -> np.ndarray:
    """k timesteps from T-1 down to 0, evenly spaced and rounded (and
    made strictly decreasing, which they already are for k ≤ T)."""
    if not 1 <= k <= T:
        raise ValueError(f"cannot respace {T} timesteps to {k}")
    ts = np.asarray(jnp.linspace(T - 1, 0, k).round().astype(jnp.int32))
    i = np.arange(k)
    ts = np.minimum.accumulate(ts + i) - i
    return np.maximum(ts, k - 1 - i).astype(np.int32)


def _ln(x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _lin(p, x, prec):
    y = jnp.matmul(x, p["w"].astype(x.dtype), precision=prec)
    return y + p["b"].astype(x.dtype) if "b" in p else y


def forward(params, spec: dict, x, t, y, *, dtype=jnp.float32,
            precision=HIGHEST):
    """ε(x_t, t, y) for a batch: x (B, H, W, C), t (B,) int, y (B, c),
    every parameter and activation in ``dtype``."""
    B, H, W, C = x.shape
    p, d, nh = spec["patch"], spec["d_model"], spec["num_heads"]
    hd = d // nh
    x = x.astype(dtype)
    tok = x.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
    tok = tok.reshape(B, (H // p) * (W // p), p * p * C)
    tok = (_lin(params["patch_in"], tok, precision)
           + params["pos"].astype(dtype))
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    ang = t[:, None].astype(jnp.float32) * freqs[None]
    temb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1).astype(dtype)
    y = y.astype(dtype)
    c = _lin(params["t_mlp2"],
             jax.nn.silu(_lin(params["t_mlp1"], temb, precision)),
             precision)
    c = jax.nn.silu(c + _lin(params["y_proj"], y, precision))
    ytok = _lin(params["cond_tok"], y, precision)
    tok = jnp.concatenate([ytok[:, None], tok], axis=1)
    S = tok.shape[1]
    for blk in params["blocks"]:
        m = _lin(blk["mod"], c, precision)
        sh1, sc1, g1, sh2, sc2, g2 = [v[:, None] for v in jnp.split(m, 6, -1)]
        h = _ln(tok) * (1 + sc1) + sh1
        qkv = _lin(blk["wqkv"], h, precision).reshape(B, S, 3, nh, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        a = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision)
        a = jax.nn.softmax(a * hd ** -0.5, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=precision)
        tok = tok + g1 * _lin(blk["wo"], o.reshape(B, S, d), precision)
        h = _ln(tok) * (1 + sc2) + sh2
        h = jax.nn.gelu(_lin(blk["w_up"], h, precision))
        h = _lin(blk["w_down"], h, precision)
        tok = tok + g2 * h
    shift, scale = jnp.split(_lin(params["out_mod"], c, precision), 2,
                             -1)
    tok = _ln(tok[:, 1:]) * (1 + scale[:, None]) + shift[:, None]
    eps = _lin(params["patch_out"], tok, precision)
    eps = eps.reshape(B, H // p, W // p, p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return eps.reshape(B, H, W, C).astype(jnp.float32)


@partial(jax.jit, static_argnames=("spec_items", "T", "dtype", "precision"))
def _trajectories(params, y, null, s, keys, ts, ab_t, ab_prev, *,
                  spec_items, T, dtype, precision):
    """Guided ancestral trajectories of a block of rows sharing one step
    count k: ``ts``/``ab_t``/``ab_prev`` are (k,), the rest per row."""
    spec = dict(spec_items)
    n, H, C = y.shape[0], spec["latent_size"], spec["channels"]
    x = jax.vmap(lambda r: jax.random.normal(jax.random.fold_in(r, 0),
                                             (H, H, C)))(keys)
    yy = jnp.concatenate([y, null], axis=0)
    sv = s[:, None, None, None]

    def step(x, inp):
        j, t, abt, abp = inp
        tt = jnp.full((2 * n,), t, jnp.int32)
        e = forward(params, spec, jnp.concatenate([x, x]), tt, yy,
                    dtype=dtype, precision=precision)
        eps = (1.0 + sv) * e[:n] - sv * e[n:]
        x0 = jnp.clip((x - jnp.sqrt(1.0 - abt) * eps) / jnp.sqrt(abt), -1, 1)
        var = (1.0 - abp) / (1.0 - abt) * (1.0 - abt / abp)
        sigma = jnp.sqrt(jnp.maximum(var, 0.0))
        dcoef = jnp.sqrt(jnp.maximum(1.0 - abp - sigma ** 2, 0.0))
        z = jax.vmap(lambda r: jax.random.normal(jax.random.fold_in(r, j + 1),
                                                 (H, H, C)))(keys)
        z = z * (t > 0)
        return jnp.sqrt(abp) * x0 + dcoef * eps + sigma * z, None

    k = ts.shape[0]
    x, _ = jax.lax.scan(step, x, (jnp.arange(k, dtype=jnp.uint32), ts, ab_t,
                                  ab_prev))
    return jnp.clip(x, -1.0, 1.0)


def row_keys(drain_key, rids, idx):
    """Per-row keys ``fold_in(fold_in(drain_key, rid), i)``."""
    return jax.vmap(lambda r, i: jax.random.fold_in(
        jax.random.fold_in(drain_key, r), i))(
            jnp.asarray(rids, jnp.uint32), jnp.asarray(idx, jnp.uint32))


def sample_rows(params, spec: dict, rows: list[dict], drain_key, *,
                T: int = 1000, dtype=jnp.float32, precision=HIGHEST,
                block: int = 8) -> np.ndarray:
    """Reference images of ``rows``, in their order.  A row is a dict with
    ``cond`` (c,) or None for an unconditional draw, ``guidance``,
    ``steps``, ``rid`` and ``index``.  Rows are run ``block`` at a time,
    grouped by step count; a short block is padded with copies of its
    last row, so each step count compiles one program."""
    ab = cosine_alpha_bar(T)
    null = np.asarray(params["null_y"], np.float32)
    items = tuple(sorted(spec.items()))
    out = [None] * len(rows)
    by_k: dict[int, list[int]] = {}
    for i, r in enumerate(rows):
        by_k.setdefault(int(r["steps"]), []).append(i)
    for k, members in sorted(by_k.items()):
        ts = respaced(T, k)
        ab_t = ab[ts]
        ab_prev = jnp.concatenate([ab[ts[1:]], jnp.ones((1,), ab.dtype)])
        for lo in range(0, len(members), block):
            sel = members[lo:lo + block]
            sel = sel + [sel[-1]] * (block - len(sel))   # one shape a k
            y = np.stack([null if rows[i]["cond"] is None
                          else np.asarray(rows[i]["cond"], np.float32)
                          for i in sel])
            s = np.array([0.0 if rows[i]["cond"] is None
                          else rows[i]["guidance"] for i in sel], np.float32)
            keys = row_keys(drain_key, [rows[i]["rid"] for i in sel],
                            [rows[i]["index"] for i in sel])
            x = _trajectories(params, jnp.asarray(y),
                              jnp.broadcast_to(null, y.shape), jnp.asarray(s),
                              keys, jnp.asarray(ts), ab_t, ab_prev,
                              spec_items=items, T=T, dtype=dtype,
                              precision=precision)
            x = np.asarray(x)
            for j, i in enumerate(sel):
                if out[i] is None:
                    out[i] = x[j]
    return np.stack(out)
