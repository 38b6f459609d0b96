"""The fused denoiser's Pallas kernels compile for a TPU v5e.

Interpret mode checks none of Mosaic's rules (block tiling, memory
spaces), so every kernel on the fused path is compiled here for a
described ``v5e:2x2`` chip at the "paper" preset's wave shapes: the
DiT's S = 17 tokens padded to 24, d_model 144 over 4 heads (head_dim 36),
16×16×3 images in (8, 128) lane blocks.  Nothing runs; the compiler only
has to accept each kernel and keep it as a ``tpu_custom_call``.

The kernel functions are called with ``interpret=False`` directly: the
``ops.py`` wrappers still see the CPU backend here.  The whole fused wave
program is compiled too, with the wrappers steered to the compiled path
by the test.  So is the default (``use_pallas=False``) DiT forward,
with the backend steered to "tpu" by the test, to show where it
dispatches the ``dit_attention`` kernel.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.oscar import DiffusionConfig, paper_preset
from repro.diffusion.dit import dit_apply, init_dit
from repro.diffusion.guidance import ragged_tables
from repro.diffusion.sampler import _ragged_core
from repro.diffusion.schedule import make_schedule
from repro.kernels import compiled_kernels
from repro.kernels.adaln_norm import kernel as adaln
from repro.kernels.cfg_fuse import kernel as cfg
from repro.kernels.dit_attention import kernel as dit_attn
from repro.kernels.flash_attention import kernel as flash


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written to the cache but cannot be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16
B_CFG, R = 128, 8          # one image = 768 floats -> 6 lane rows, padded to 8
B_DIT, S, TRUE_S, HEADS, HD, D = 256, 24, 17, 4, 36, 144

CASES = {
    "cfg_fuse_rowwise": (
        lambda x, ec, eu, z, off, scal: cfg.cfg_update_rowwise_3d(
            x, ec, eu, z, off, scal, interpret=False),
        [((B_CFG, R, 128), F32)] * 4 + [((1,), I32), ((4, B_CFG), F32)]),
    "cfg_fuse_mixed": (
        lambda x, ec, eu, z, off, scal: cfg.cfg_update_mixed_3d(
            x, ec, eu, z, off, scal, interpret=False),
        [((B_CFG, R, 128), F32)] * 4 + [((1,), I32), ((5, B_CFG), F32)]),
    "cfg_fuse": (
        lambda x, ec, eu, z, ab_t, ab_prev: cfg.cfg_update_2d(
            x, ec, eu, z, ab_t, ab_prev, s=2.0, interpret=False),
        [((B_CFG * R, 128), F32)] * 4 + [((), F32)] * 2),
    "flash_attention": (
        lambda q, k, v: flash.flash_attention_bhsd(
            q, k, v, causal=False, blk_q=S, blk_k=S, true_sk=TRUE_S,
            interpret=False),
        [((B_DIT, HEADS, S, HD), F32)] * 3),
    # b2.round_drain's attention core: 240 sequences of DiT-B/2
    "dit_attention": (
        lambda qkv: dit_attn.dit_attention_3d(qkv, heads=12, interpret=False),
        [((240, 257, 3 * 768), BF16)]),
    "adaln_norm": (
        lambda x, scale, shift: adaln.adaln_norm_3d(x, scale, shift,
                                                    interpret=False),
        [((B_DIT, S, D), F32), ((B_DIT, D), F32), ((B_DIT, D), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    text = _compile(fn, one_chip, *shapes)
    assert 'custom_call_target="tpu_custom_call"' in text
    assert compiled_kernels(text) == {name}


def test_fused_wave_program_compiles_for_v5e(one_chip, monkeypatch):
    """The merged ragged wave of the paper preset's OSCAR round (600 rows
    in 5 waves of 120, T = 50) with ``use_pallas=True``: one program that
    keeps all three kernel families as ``tpu_custom_call`` ops."""
    from repro.kernels.adaln_norm import ops as adaln_ops
    from repro.kernels.cfg_fuse import ops as cfg_ops
    from repro.kernels.flash_attention import ops as flash_ops
    for ops in (adaln_ops, cfg_ops, flash_ops):
        monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    ocfg = paper_preset()
    dc, size = ocfg.diffusion, ocfg.data.image_size
    B, steps = 120, dc.sample_timesteps

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: init_dit(k, dc, size, ocfg.data.channels),
        jax.random.PRNGKey(0)))
    row_keys = jax.eval_shape(lambda: jax.random.split(
        jax.random.PRNGKey(0), B))
    tables = ragged_tables(make_schedule(dc.train_timesteps, dc.schedule),
                           np.full(B, steps, np.int32), steps)
    text = _ragged_core.lower(
        params, dc, sds(jax.ShapeDtypeStruct((B, dc.cond_dim), F32)),
        sds(row_keys), sds(jax.ShapeDtypeStruct((B,), F32)),
        *[sds(t) for t in tables], image_size=size,
        channels=ocfg.data.channels, eta=1.0,
        use_pallas=True).compile().as_text()
    assert compiled_kernels(text) == {"cfg_fuse_rowwise", "flash_attention",
                                      "adaln_norm"}


@pytest.mark.parametrize("dc,size,channels,kernels", [
    (DiffusionConfig(d_model=768, num_layers=1, num_heads=12, patch=2),
     32, 4, {"dit_attention"}),                       # DiT-B/2, S = 257
    (paper_preset().diffusion, paper_preset().data.image_size,
     paper_preset().data.channels, set()),             # S = 17
])
def test_default_dit_forward_dispatch_for_v5e(one_chip, monkeypatch, dc, size,
                                              channels, kernels):
    """The default path on the TPU runs the attention core through
    ``dit_attention`` from ``MIN_SEQ`` tokens and keeps the einsum chain
    below: one 240-sequence forward at DiT-B/2's widths (one block) and
    at the paper preset's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B = 240

    def sds(shape, dt=F32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda k: init_dit(k, dc, size, channels), jax.random.PRNGKey(0)))
    text = jax.jit(lambda p, x, t, y: dit_apply(p, dc, x, t, y)).lower(
        params, sds((B, size, size, channels)), sds((B,), I32),
        sds((B, dc.cond_dim))).compile().as_text()
    assert compiled_kernels(text) == kernels
