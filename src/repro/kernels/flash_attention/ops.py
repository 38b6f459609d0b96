"""Public wrapper: (B, S, H, hd) layout, padding to block multiples, GQA;
interpret mode on the CPU (``repro.kernels.interpret_mode``)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention import kernel as K


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, interpret: bool | None = None):
    """q: (B, Sq, Hq, hd); k/v: (B, Sk, Hkv, hd) → (B, Sq, Hq, hd)."""
    if interpret is None:
        interpret = interpret_mode()
    B, Sq, Hq, hd = q.shape
    Sk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # Blocks rounded up to the 8-row sublane multiple: an S = n_tok+1
    # sequence (odd by construction — e.g. 17, 65 from the DiT's prepended
    # conditioning token) pads to an aligned block instead of launching a
    # misaligned one; the kernel masks the padded K rows via true_sk.
    blk_q = min(K.DEFAULT_BLOCK_Q, max(8, -(-Sq // 8) * 8))
    blk_k = min(K.DEFAULT_BLOCK_K, max(8, -(-Sk // 8) * 8))
    pad_q = (-Sq) % blk_q
    pad_k = (-Sk) % blk_k
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    out = K.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                 softcap=softcap, blk_q=blk_q, blk_k=blk_k,
                                 interpret=interpret, true_sk=Sk)
    if pad_q:
        out = out[:, :, :Sq, :]
    return out.transpose(0, 2, 1, 3)
