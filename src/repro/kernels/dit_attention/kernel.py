"""Pallas TPU attention core of a DiT block: one grid step per sequence,
all of its keys and values resident in VMEM, so each head's S×S scores
live and die in VMEM and the softmax is a plain single pass (no online
rescaling, no key blocks).

The kernel reads the QKV product in the layout the projection writes,
``(B, S, 3d)``, and writes ``(B, S, d)``, the layout the output
projection reads: no head split or merge goes through HBM.  Heads are a
static loop inside the step, each a slice of ``hd`` lanes.

Precision is the configuration's default matmul precision on the TPU:
both products take bfloat16 operands and accumulate in float32; the
scores, max, exp, sum and normalisation are float32, and p is
normalised before its bfloat16 cast, as the einsum chain in ``ref.py``
does."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dit_attention_kernel(qkv_ref, o_ref, *, heads, scale):
    d = o_ref.shape[-1]
    hd = d // heads
    for h in range(heads):
        lo = h * hd
        q = qkv_ref[0, :, lo:lo + hd]                       # (S, hd) bf16
        k = qkv_ref[0, :, d + lo:d + lo + hd]
        v = qkv_ref[0, :, 2 * d + lo:2 * d + lo + hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        o = jax.lax.dot_general(p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[0, :, lo:lo + hd] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def dit_attention_3d(qkv, *, heads: int, interpret: bool = False):
    """qkv: (B, S, 3d) bfloat16 → (B, S, d) float32."""
    B, S, d3 = qkv.shape
    d = d3 // 3
    return pl.pallas_call(
        functools.partial(_dit_attention_kernel, heads=heads,
                          scale=(d // heads) ** -0.5),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, S, d3), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, S, d), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="dit_attention",
    )(qkv)
