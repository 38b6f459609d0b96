"""Pallas kernels of the fused denoiser path (``cfg_fuse``,
``flash_attention``, ``adaln_norm``), the default path's attention core
on the TPU (``dit_attention``), plus ``rmsnorm``.

Each family has ``kernel.py`` (the ``pallas_call``, named after the
family), ``ops.py`` (the public wrapper) and ``ref.py`` (the jnp oracle).
The kernels are written for the TPU.  On the CPU they run in Pallas
interpret mode, which is how the tests exercise them; any other backend
is an error, never a silent fallback."""
from __future__ import annotations

import re

import jax


def interpret_mode() -> bool:
    """The ``interpret`` flag the ops wrappers default to: True on the
    CPU (tests), False on the TPU (compiled Mosaic kernels).  Raises on
    any other backend."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for the TPU and interpret on the CPU; "
        f"backend {backend!r} is neither")


def compiled_kernels(hlo_text: str) -> set[str]:
    """Names of the Pallas kernels compiled into a TPU program: every
    ``tpu_custom_call`` op of ``compiled.as_text()`` carries its
    ``pallas_call`` name in its ``op_name`` metadata.  An interpreted
    kernel, or one replaced by its reference, leaves no such op."""
    return {m.group(1) for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in re.finditer(r'/([A-Za-z0-9_]+)/pallas_call', line)}
