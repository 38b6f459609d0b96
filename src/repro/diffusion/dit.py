"""Conditional DiT denoiser ε_θ(x_t, t, y) — the in-repo stand-in for
Stable Diffusion (DESIGN.md §8).

TPU-native choice: pure matmul pipeline (patchify → adaLN-zero transformer
→ unpatchify), conditioned on a 512-d encoding vector (the CLIP-embedding
slot of the OSCAR pipeline) via adaLN modulation.  A learned null embedding
Ø implements classifier-free training/sampling (Ho & Salimans).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.oscar import DiffusionConfig
from repro.kernels.dit_attention import ops as da_ops
from repro.kernels.dit_attention import ref as da_ref
from repro.utils import lecun_init, normal_init, zeros_init


#: the named scope of each DiT sub-block, in the order a forward runs them
DIT_SCOPES = ("dit.embed", "dit.mod", "dit.attn.qkv", "dit.attn.core",
              "dit.attn.out", "dit.mlp", "dit.head")


def timestep_embedding(t, dim: int, max_period: float = 10_000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    ang = t[:, None].astype(jnp.float32) * freqs[None]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def init_dit(key, dc: DiffusionConfig, image_size: int, channels: int):
    d, p = dc.d_model, dc.patch
    n_tok = (image_size // p) ** 2
    patch_dim = p * p * channels
    ks = jax.random.split(key, 8 + 6 * dc.num_layers)
    params = {
        "patch_in": {"w": lecun_init(ks[0], (patch_dim, d)),
                     "b": zeros_init(ks[0], (d,))},
        "pos": normal_init(ks[1], (n_tok, d), stddev=0.02),
        "t_mlp1": {"w": lecun_init(ks[2], (d, d)), "b": zeros_init(ks[2], (d,))},
        "t_mlp2": {"w": lecun_init(ks[3], (d, d)), "b": zeros_init(ks[3], (d,))},
        "y_proj": {"w": lecun_init(ks[4], (dc.cond_dim, d)),
                   "b": zeros_init(ks[4], (d,))},
        "null_y": normal_init(ks[5], (dc.cond_dim,), stddev=0.5),
        "out_mod": {"w": zeros_init(ks[6], (d, 2 * d)), "b": zeros_init(ks[6], (2 * d,))},
        "patch_out": {"w": zeros_init(ks[7], (d, patch_dim)),
                      "b": zeros_init(ks[7], (patch_dim,))},
        # conditioning token: gives attention direct access to y (in
        # addition to adaLN modulation) — SD-style cross-attn analogue
        "cond_tok": {"w": lecun_init(jax.random.fold_in(key, 99), (dc.cond_dim, d)),
                     "b": zeros_init(ks[7], (d,))},
        "blocks": [],
    }
    blocks = []
    for i in range(dc.num_layers):
        k6 = ks[8 + 6 * i: 14 + 6 * i]
        blocks.append({
            "wqkv": {"w": lecun_init(k6[0], (d, 3 * d))},
            "wo": {"w": lecun_init(k6[1], (d, d))},
            "w_up": {"w": lecun_init(k6[2], (d, 4 * d)), "b": zeros_init(k6[2], (4 * d,))},
            "w_down": {"w": lecun_init(k6[3], (4 * d, d)), "b": zeros_init(k6[3], (d,))},
            # adaLN-zero: 6 modulation vectors, zero-init
            "mod": {"w": zeros_init(k6[4], (d, 6 * d)), "b": zeros_init(k6[4], (6 * d,))},
        })
    params["blocks"] = blocks
    return params


def _ln(x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def _dense_act(p, x, bf16: bool):
    """_dense with optional bf16 activations/weights, fp32 accumulation."""
    if not bf16:
        return _dense(p, x)
    y = jax.lax.dot_general(
        x.astype(jnp.bfloat16), p["w"].astype(jnp.bfloat16),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if "b" in p:
        y = y + p["b"]
    return y


def _modulated_ln(x, scale, shift, fused: bool):
    """One DiT modulation site: LayerNorm + adaLN ``(1+scale)·x̂+shift``."""
    if fused:
        from repro.kernels.adaln_norm import ops as an_ops
        return an_ops.adaln_norm(x, scale, shift)
    return _ln(x) * (1 + scale[:, None]) + shift[:, None]


def _attention_core(qkv, heads: int, fused: bool):
    """Non-causal attention of one block: the QKV product (B, S, 3d) →
    (B, S, d), by the kernel the path and the shape call for."""
    if fused:
        from repro.kernels.flash_attention import ops as fa_ops
        B, S, _ = qkv.shape
        qkv = qkv.reshape(B, S, 3, heads, -1)
        o = fa_ops.flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                                   causal=False)
        return o.reshape(B, S, -1)
    if da_ops.engages(qkv.shape[1]):
        return da_ops.dit_attention(qkv, heads)
    return da_ref.dit_attention(qkv, heads)


def patchify(x, p: int):
    B, H, W, C = x.shape
    x = x.reshape(B, H // p, p, W // p, p, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def unpatchify(tok, p: int, H: int, W: int, C: int):
    B = tok.shape[0]
    x = tok.reshape(B, H // p, W // p, p, p, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H, W, C)


def dit_apply(params, dc: DiffusionConfig, x_t, t, y, *,
              heads: int | None = None, use_pallas: bool = False):
    """ε-prediction.  x_t: (B,H,W,C); t: (B,) int32; y: (B, cond_dim) or
    None (→ null embedding Ø).

    The default path runs each block's attention core as the einsum chain
    of ``kernels.dit_attention.ref``, except where
    ``kernels.dit_attention.ops.engages`` says the kernel applies (on the
    TPU, for ``MIN_SEQ`` ≤ S ≤ ``MAX_SEQ``).  There the core is the
    ``dit_attention`` kernel, whose products take the bfloat16 operands
    XLA gives the chain at the default matmul precision, and which keeps
    the S×S scores in VMEM.  On the CPU the default path is the chain.

    ``use_pallas`` (or ``dc.use_pallas``) swaps the attention core for
    ``kernels.flash_attention`` (non-causal, S = n_tok+1) and the three
    LN+modulation sites for ``kernels.adaln_norm``; fp32 output matches the
    naive path within float tolerance.  ``dc.bf16_act`` additionally runs
    the QKV/MLP matmuls with bf16 activations + fp32 accumulation (fused
    path only)."""
    fused = use_pallas or getattr(dc, "use_pallas", False)
    bf16 = fused and getattr(dc, "bf16_act", False)
    B, H, W, C = x_t.shape
    p = dc.patch
    nh = heads or dc.num_heads
    with jax.named_scope("dit.embed"):
        tok = _dense(params["patch_in"], patchify(x_t, p)) + params["pos"]
        temb = timestep_embedding(t, dc.d_model)
        c = _dense(params["t_mlp2"],
                   jax.nn.silu(_dense(params["t_mlp1"], temb)))
        if y is None:
            y = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
        c = c + _dense(params["y_proj"], y.astype(jnp.float32))
        c = jax.nn.silu(c)
        # prepend the conditioning token (sliced off before unpatchify)
        ytok = _dense(params["cond_tok"], y.astype(jnp.float32))[:, None, :]
        tok = jnp.concatenate([ytok, tok], axis=1)

    for blk in params["blocks"]:
        with jax.named_scope("dit.mod"):
            mod = _dense(blk["mod"], c)                   # (B, 6d)
            (sa_shift, sa_scale, sa_gate,
             ml_shift, ml_scale, ml_gate) = jnp.split(mod, 6, -1)
        with jax.named_scope("dit.attn.qkv"):
            h = _modulated_ln(tok, sa_scale, sa_shift, fused)
            qkv = _dense_act(blk["wqkv"], h, bf16)
        with jax.named_scope("dit.attn.core"):
            o = _attention_core(qkv, nh, fused)
        with jax.named_scope("dit.attn.out"):
            tok = tok + sa_gate[:, None] * _dense_act(blk["wo"], o, bf16)
        with jax.named_scope("dit.mlp"):
            h = _modulated_ln(tok, ml_scale, ml_shift, fused)
            h = _dense_act(blk["w_down"],
                           jax.nn.gelu(_dense_act(blk["w_up"], h, bf16)),
                           bf16)
            tok = tok + ml_gate[:, None] * h

    with jax.named_scope("dit.head"):
        tok = tok[:, 1:]   # drop the conditioning token
        shift, scale = jnp.split(_dense(params["out_mod"], c), 2, -1)
        tok = _modulated_ln(tok, scale, shift, fused)
        eps = _dense(params["patch_out"], tok)
        return unpatchify(eps, p, H, W, C)
