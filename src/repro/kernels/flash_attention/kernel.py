"""Pallas TPU flash attention (forward).

Canonical online-softmax blocking re-thought for the MXU/VMEM hierarchy
(DESIGN.md §4): 128-aligned Q/KV blocks stream through VMEM; the running
(m, l, acc) state lives in VMEM scratch and persists across the sequential
kv-block grid axis.  Supports the zoo's variants: GQA (q-head → kv-head
mapping in the index maps), causal masks, sliding windows (gemma2 local
layers), attention-logit softcap (gemma2), encoder (non-causal) mode.

Grid: (B, H_q, n_q_blocks, n_kv_blocks) — the last axis is 'arbitrary'
(sequential); fully-masked kv blocks are skipped with pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, softcap, blk_q, blk_k, nk, sq, sk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    kpos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)

    # block-level skip: any work in this (iq, ik) tile?
    needed = jnp.bool_(True)
    if causal:
        needed &= (ik * blk_k) <= (iq * blk_q + blk_q - 1)
    if window:
        needed &= (ik * blk_k + blk_k - 1) > (iq * blk_q - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (blk_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (blk_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos < sk
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)
        m_prev = m_scr[...][:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_scr[...][:, 0] + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "blk_q", "blk_k",
                     "interpret", "true_sk"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         softcap: float = 0.0, blk_q: int = DEFAULT_BLOCK_Q,
                         blk_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False, true_sk: int | None = None):
    """q: (B, Hq, Sq, hd); k/v: (B, Hkv, Sk, hd); Sq, Sk padded to blocks
    by the ops wrapper.  Returns (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Sk)
    nq = pl.cdiv(Sq, blk_q)
    nk = pl.cdiv(Sk, blk_k)
    scale = hd ** -0.5

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, blk_q=blk_q, blk_k=blk_k, nk=nk, sq=Sq,
        sk=true_sk or Sk)

    return pl.pallas_call(
        kern,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, i, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, i, j: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
