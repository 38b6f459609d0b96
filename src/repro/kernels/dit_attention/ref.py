"""Pure-jnp oracle for the DiT attention core: the ``einsum → softmax →
einsum`` chain ``repro.diffusion.dit`` runs wherever the kernel is not
dispatched, in the layout the kernel reads and writes.

``qkv`` is the QKV product ``(B, S, 3d)``: q, k and v side by side, each
``heads`` heads of ``d // heads``.  The result is ``(B, S, d)`` with the
heads side by side, the layout the output projection consumes."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dit_attention(qkv, heads: int):
    B, S, d3 = qkv.shape
    hd = d3 // (3 * heads)
    qkv = qkv.reshape(B, S, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    attn = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", attn, v)
    return o.reshape(B, S, heads * hd)
