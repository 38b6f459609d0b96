"""Public wrapper for the DiT attention kernel: the bfloat16 operands,
a backward pass, and the rule by which the default DiT path dispatches
it; interpret mode on the CPU (``repro.kernels.interpret_mode``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.dit_attention import kernel as K
from repro.kernels.dit_attention import ref

#: the sequences whose attention core the default DiT path runs through
#: the kernel.  Below MIN_SEQ the einsum chain is faster on a v5e; MAX_SEQ
#: (a 32×32 patch grid and the conditioning token) is the longest
#: measured, and at 4097 one head's S×S scores no longer fit VMEM.
MIN_SEQ, MAX_SEQ = 128, 1025


def engages(seq_len: int) -> bool:
    """Whether the default DiT path runs its attention core through the
    kernel: on the TPU, at the default matmul precision the kernel keeps
    (one bfloat16 pass), from ``MIN_SEQ`` to ``MAX_SEQ`` tokens, where the
    einsum chain writes its float32 S×S scores to HBM and reads them
    back.  Only in a process with one device: XLA cannot partition a
    Mosaic kernel over a program that spans several, and which devices a
    program spans is not known while it is traced."""
    return (jax.default_backend() == "tpu" and MIN_SEQ <= seq_len <= MAX_SEQ
            and jax.config.jax_default_matmul_precision is None
            and jax.device_count() == 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def dit_attention(qkv, heads: int):
    """qkv: (B, S, 3d) QKV product → (B, S, d) float32 attention output.

    Both products take bfloat16 operands, which is what XLA gives the
    einsum chain's products at the default precision.  The backward pass
    is the VJP of ``ref.dit_attention``, recomputed from the saved
    ``qkv``."""
    return K.dit_attention_3d(qkv.astype(jnp.bfloat16), heads=heads,
                              interpret=interpret_mode())


def _fwd(qkv, heads):
    return dit_attention(qkv, heads), qkv


def _bwd(heads, qkv, g):
    return jax.vjp(lambda x: ref.dit_attention(x, heads), qkv)[1](g)


dit_attention.defvjp(_fwd, _bwd)
