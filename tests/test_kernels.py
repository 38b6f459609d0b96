"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp ref.py oracles."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.cfg_fuse import ops as cfg_ops
from repro.kernels.cfg_fuse import ref as cfg_ref
from repro.kernels.dit_attention import ops as da_ops
from repro.kernels.dit_attention import ref as da_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.rmsnorm import ops as rn_ops
from repro.kernels.rmsnorm import ref as rn_ref


@pytest.mark.parametrize(
    "S,Hq,Hkv,hd,causal,window,cap,dt",
    [
        (64, 4, 4, 64, True, 0, 0.0, jnp.float32),
        (128, 4, 2, 64, True, 0, 0.0, jnp.float32),     # GQA
        (100, 8, 1, 128, True, 0, 0.0, jnp.bfloat16),   # MQA + ragged + bf16
        (128, 4, 2, 128, True, 32, 50.0, jnp.float32),  # sliding + softcap
        (96, 2, 2, 64, False, 0, 0.0, jnp.float32),     # encoder (hubert)
        (256, 4, 4, 80, True, 0, 0.0, jnp.float32),     # hd=80 (hubert)
        (32, 4, 4, 256, True, 0, 0.0, jnp.float32),     # hd=256 (gemma2)
    ])
def test_flash_attention_matches_oracle(rng_key, S, Hq, Hkv, hd, causal,
                                        window, cap, dt):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (2, S, Hq, hd), dt)
    k = jax.random.normal(ks[1], (2, S, Hkv, hd), dt)
    v = jax.random.normal(ks[2], (2, S, Hkv, hd), dt)
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 softcap=cap)
    ref = fa_ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), causal=causal,
                           window=window, softcap=cap).transpose(0, 2, 1, 3)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    assert jnp.max(jnp.abs(out.astype(jnp.float32) -
                           ref.astype(jnp.float32))) < tol


def test_flash_attention_cross_length(rng_key):
    """Sq != Sk (prefill continuation shape)."""
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 4, 64))
    v = jax.random.normal(ks[2], (1, 128, 4, 64))
    out = fa_ops.flash_attention(q, k, v, causal=False)
    ref = fa_ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3),
                           causal=False).transpose(0, 2, 1, 3)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


@pytest.mark.parametrize("shape,dt", [
    ((4, 32, 256), jnp.float32),
    ((3, 7, 512), jnp.bfloat16),
    ((128, 1024), jnp.float32),
    ((5, 96), jnp.float32),
])
def test_rmsnorm_matches_oracle(rng_key, shape, dt):
    x = jax.random.normal(rng_key, shape, dt)
    s = jax.random.normal(jax.random.fold_in(rng_key, 1), (shape[-1],)) * 0.1
    out = rn_ops.rmsnorm(x, s)
    ref = rn_ref.rmsnorm(x, s)
    tol = 5e-2 if dt == jnp.bfloat16 else 1e-5
    assert jnp.max(jnp.abs(out.astype(jnp.float32) -
                           ref.astype(jnp.float32))) <= tol


@pytest.mark.parametrize("shape", [(4, 16, 16, 3), (7, 8, 8, 1), (1, 33)])
@pytest.mark.parametrize("s,ab_t,ab_prev", [
    (7.5, 0.31, 0.52), (0.0, 0.9, 0.95), (3.0, 0.05, 0.11)])
def test_cfg_fuse_matches_oracle(rng_key, shape, s, ab_t, ab_prev):
    ks = jax.random.split(rng_key, 4)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    out = cfg_ops.cfg_update(x, ec, eu, s, ab_t, ab_prev, z)
    ref = cfg_ref.cfg_update(x, ec, eu, s, ab_t, ab_prev, z)
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


def test_cfg_guidance_zero_is_conditional(rng_key):
    """s=0 ⇒ ε̂ = ε_c exactly (Eq. 8 degenerate case)."""
    ks = jax.random.split(rng_key, 4)
    x, ec, eu, z = (jax.random.normal(k, (2, 8, 8, 3)) for k in ks)
    a = cfg_ref.cfg_update(x, ec, eu, 0.0, 0.5, 0.7, z)
    b = cfg_ref.ancestral_step(x, ec, 0.5, 0.7, z)
    assert jnp.allclose(a, b)


@pytest.mark.parametrize("rows", [8, 248, 304, 520])
def test_cfg_fuse_partial_blocks(rng_key, rows):
    """Row counts around/above BLOCK_ROWS=256, incl. non-divisible grids —
    the (rows, 128) layout exercises partial trailing blocks directly."""
    ks = jax.random.split(rng_key, 4)
    shape = (rows, 128)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    out = cfg_ops.cfg_update(x, ec, eu, 3.0, 0.3, 0.6, z)
    ref = cfg_ref.cfg_update(x, ec, eu, 3.0, 0.3, 0.6, z)
    assert out.shape == shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


def test_cfg_fuse_ragged_flatten(rng_key):
    """A shape whose flat size divides neither 128 lanes nor the 8-row
    sublane alignment — ops.py must pad and exactly un-pad."""
    ks = jax.random.split(rng_key, 4)
    shape = (5, 97, 13)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    out = cfg_ops.cfg_update(x, ec, eu, 1.5, 0.2, 0.4, z)
    ref = cfg_ref.cfg_update(x, ec, eu, 1.5, 0.2, 0.4, z)
    assert out.shape == shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


@pytest.mark.parametrize("shape", [(6, 16, 16, 3), (3, 8, 8, 1), (2, 33),
                                   (5, 97, 13)])
def test_cfg_fuse_rowwise_matches_oracle(rng_key, shape):
    """Ragged-wave kernel: per-row (s, ᾱ_t, ᾱ_prev, active) scalars vs the
    rowwise jnp oracle, incl. non-lane-aligned per-image flatten."""
    B = shape[0]
    ks = jax.random.split(rng_key, 4)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    s = jnp.linspace(0.0, 7.5, B)
    ab_t = jnp.linspace(0.05, 0.9, B)
    ab_prev = jnp.linspace(0.11, 0.95, B)
    act = (jnp.arange(B) % 3 != 1).astype(jnp.float32)
    out = cfg_ops.cfg_update_rowwise(x, ec, eu, s, ab_t, ab_prev, z, act)
    ref = cfg_ref.cfg_update_rowwise(x, ec, eu, s, ab_t, ab_prev, z, act)
    assert out.shape == shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


def test_cfg_fuse_rowwise_uniform_matches_scalar_kernel(rng_key):
    """All rows agreeing on (s, ᾱ_t, ᾱ_prev) must reproduce the scalar
    cfg_fuse kernel BIT-exactly — the contract that lets ragged waves
    replace per-group waves without changing a single pixel."""
    ks = jax.random.split(rng_key, 4)
    shape = (4, 16, 16, 3)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    for s, ab_t, ab_prev in [(7.5, 0.31, 0.52), (0.0, 0.9, 0.95),
                             (1.5, 0.05, 0.11)]:
        row = cfg_ops.cfg_update_rowwise(
            x, ec, eu, jnp.full((4,), s), jnp.full((4,), ab_t),
            jnp.full((4,), ab_prev), z, jnp.ones((4,)))
        scal = cfg_ops.cfg_update(x, ec, eu, s, ab_t, ab_prev, z)
        assert jnp.array_equal(row, scal)


def test_cfg_fuse_rowwise_inactive_rows_frozen(rng_key):
    """active=0 rows pass through bit-unchanged (the right-aligned ragged
    freeze), in both the kernel and the oracle."""
    ks = jax.random.split(rng_key, 4)
    shape = (5, 8, 8, 3)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    act = jnp.array([1.0, 0.0, 1.0, 0.0, 0.0])
    s = jnp.full((5,), 7.5)
    ab_t, ab_prev = jnp.full((5,), 0.31), jnp.full((5,), 0.52)
    out = cfg_ops.cfg_update_rowwise(x, ec, eu, s, ab_t, ab_prev, z, act)
    ref = cfg_ref.cfg_update_rowwise(x, ec, eu, s, ab_t, ab_prev, z, act)
    for b, a in enumerate([1, 0, 1, 0, 0]):
        if a:
            assert not jnp.array_equal(out[b], x[b])
        else:
            assert jnp.array_equal(out[b], x[b])
            assert jnp.array_equal(ref[b], x[b])


@pytest.mark.parametrize("off,B,Bs", [(0, 4, 4), (0, 3, 8), (2, 3, 8),
                                      (5, 3, 8), (3, 5, 8)])
def test_cfg_fuse_rowwise_segment_offset(rng_key, off, B, Bs):
    """Segment-offset scalar-prefetch path: the per-row scalar table
    spans a full wave (Bs rows) while the launch updates a window of B
    tensor rows starting at ``row_offset`` — tensor row b must read
    scalar slot off+b, exactly the windowed oracle."""
    ks = jax.random.split(rng_key, 4)
    shape = (B, 8, 8, 3)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    s = jnp.linspace(0.0, 7.5, Bs)
    ab_t = jnp.linspace(0.05, 0.9, Bs)
    ab_prev = jnp.linspace(0.11, 0.95, Bs)
    act = (jnp.arange(Bs) % 2 == 0).astype(jnp.float32)
    out = cfg_ops.cfg_update_rowwise(x, ec, eu, s, ab_t, ab_prev, z, act,
                                     row_offset=off)
    ref = cfg_ref.cfg_update_rowwise_windowed(x, ec, eu, s, ab_t, ab_prev,
                                              z, act, row_offset=off)
    assert out.shape == shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-5
    # and the window is bit-equal to slicing the scalars up front — the
    # offset only changes addressing, never arithmetic
    w = slice(off, off + B)
    sliced = cfg_ops.cfg_update_rowwise(x, ec, eu, s[w], ab_t[w],
                                        ab_prev[w], z, act[w])
    assert jnp.array_equal(out, sliced)


def test_cfg_fuse_rowwise_offset_out_of_range_refuses(rng_key):
    """A window past the scalar table's rows must refuse loudly — serving
    row b with another row's (ᾱ, s) would corrupt a whole trajectory."""
    ks = jax.random.split(rng_key, 4)
    x, ec, eu, z = (jax.random.normal(k, (4, 8, 8, 3)) for k in ks)
    v = jnp.linspace(0.1, 0.9, 6)
    with pytest.raises(ValueError, match="out of range"):
        cfg_ops.cfg_update_rowwise(x, ec, eu, v, v, v, z, jnp.ones((6,)),
                                   row_offset=3)
    # negative offsets would silently wrap the scalar reads on CPU (and
    # are out-of-bounds UB on TPU) — refuse them the same way
    with pytest.raises(ValueError, match="out of range"):
        cfg_ops.cfg_update_rowwise(x, ec, eu, v, v, v, z, jnp.ones((6,)),
                                   row_offset=-2)


def test_cfg_fuse_rowwise_bf16(rng_key):
    """bf16 rows: f32 accumulation, one rounding on store — within one
    bf16 ulp of the f32 oracle, dtype preserved."""
    ks = jax.random.split(rng_key, 4)
    shape = (4, 16, 16, 3)
    x, ec, eu, z = (jax.random.normal(k, shape, jnp.bfloat16) for k in ks)
    s = jnp.linspace(0.0, 7.5, 4)
    ab_t = jnp.linspace(0.05, 0.9, 4)
    ab_prev = jnp.linspace(0.11, 0.95, 4)
    out = cfg_ops.cfg_update_rowwise(x, ec, eu, s, ab_t, ab_prev, z,
                                     jnp.ones((4,)))
    assert out.dtype == jnp.bfloat16
    ref = cfg_ref.cfg_update_rowwise(
        x.astype(jnp.float32), ec.astype(jnp.float32),
        eu.astype(jnp.float32), s, ab_t, ab_prev, z.astype(jnp.float32),
        jnp.ones((4,)))
    err = jnp.abs(out.astype(jnp.float32) - ref)
    assert bool(jnp.all(err <= 2.0 ** -8 * jnp.maximum(jnp.abs(ref), 1.0)))


@pytest.mark.parametrize("shape", [(4, 16, 16, 3), (300, 128)])
def test_cfg_fuse_bf16(rng_key, shape):
    """bf16 inputs: kernel accumulates in f32 and rounds once on store, so
    it must stay within one bf16 ulp of the f32 oracle."""
    ks = jax.random.split(rng_key, 4)
    x, ec, eu, z = (jax.random.normal(k, shape, jnp.bfloat16) for k in ks)
    out = cfg_ops.cfg_update(x, ec, eu, 7.5, 0.31, 0.52, z)
    assert out.dtype == jnp.bfloat16
    ref = cfg_ref.cfg_update(x.astype(jnp.float32), ec.astype(jnp.float32),
                             eu.astype(jnp.float32), 7.5, 0.31, 0.52,
                             z.astype(jnp.float32))
    # bound: one bf16 ulp of the f32 result (outputs reach ~±30 at s=7.5)
    err = jnp.abs(out.astype(jnp.float32) - ref)
    assert bool(jnp.all(err <= 2.0 ** -8 * jnp.maximum(jnp.abs(ref), 1.0)))


# ---------------------------------------------------------------------------
# mixed-guidance rowwise: per-row (mode, ᾱ_t, ᾱ_prev, s, active)
# ---------------------------------------------------------------------------

def _mixed_rows(B):
    mode = (jnp.arange(B) % 2).astype(jnp.float32)
    s = jnp.linspace(0.0, 7.5, B)
    ab_t = jnp.linspace(0.05, 0.9, B)
    ab_prev = jnp.linspace(0.11, 0.95, B)
    act = (jnp.arange(B) % 3 != 1).astype(jnp.float32)
    return mode, s, ab_t, ab_prev, act


@pytest.mark.parametrize("shape", [(6, 16, 16, 3), (3, 8, 8, 1), (5, 97, 13)])
def test_cfg_fuse_mixed_matches_oracle(rng_key, shape):
    """Mixed-guidance kernel: mode-0 rows combine (1+s)ε_c − sε_u, mode-1
    rows take ε_c as the upstream-corrected ε̂ — vs the rowwise jnp
    oracle, incl. non-lane-aligned per-image flatten."""
    B = shape[0]
    ks = jax.random.split(rng_key, 4)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    mode, s, ab_t, ab_prev, act = _mixed_rows(B)
    out = cfg_ops.cfg_update_mixed(x, ec, eu, mode, s, ab_t, ab_prev, z, act)
    ref = cfg_ref.cfg_update_mixed(x, ec, eu, mode, s, ab_t, ab_prev, z, act)
    assert out.shape == shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-5


def test_cfg_fuse_mixed_all_cfg_rows_match_rowwise_kernel(rng_key):
    """mode ≡ 0 must reproduce the pure cfg rowwise kernel BIT-exactly —
    the contract that lets the engine keep dispatching the pure
    executable for clf-free waves without a parity cliff."""
    ks = jax.random.split(rng_key, 4)
    shape = (4, 16, 16, 3)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    _, s, ab_t, ab_prev, act = _mixed_rows(4)
    out = cfg_ops.cfg_update_mixed(x, ec, eu, jnp.zeros((4,)), s, ab_t,
                                   ab_prev, z, act)
    pure = cfg_ops.cfg_update_rowwise(x, ec, eu, s, ab_t, ab_prev, z, act)
    assert jnp.array_equal(out, pure)


def test_cfg_fuse_mixed_mode1_ignores_s_and_eps_u(rng_key):
    """mode-1 rows carry an already-corrected ε̂ in the ε_c slot: their
    (s, ε_u) row values must be dead — bit-equal to a mode-0 row at
    s=0, whatever garbage sits in those slots."""
    ks = jax.random.split(rng_key, 5)
    shape = (4, 8, 8, 3)
    x, ec, eu, junk = (jax.random.normal(k, shape) for k in ks[:4])
    z = jax.random.normal(ks[4], shape)
    _, _, ab_t, ab_prev, _ = _mixed_rows(4)
    ones = jnp.ones((4,))
    clf = cfg_ops.cfg_update_mixed(x, ec, junk, ones, jnp.full((4,), 7.5),
                                   ab_t, ab_prev, z, ones)
    s0 = cfg_ops.cfg_update_mixed(x, ec, eu, jnp.zeros((4,)),
                                  jnp.zeros((4,)), ab_t, ab_prev, z, ones)
    assert jnp.array_equal(clf, s0)


@pytest.mark.parametrize("off,B,Bs", [(0, 4, 4), (2, 3, 8), (3, 5, 8)])
def test_cfg_fuse_mixed_segment_offset(rng_key, off, B, Bs):
    """Segment-offset scalar-prefetch path for mixed waves: the (5, Bs)
    scalar table spans the wave, tensor row b reads slot off+b — exactly
    the windowed oracle, and bit-equal to slicing the table up front."""
    ks = jax.random.split(rng_key, 4)
    shape = (B, 8, 8, 3)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    mode, s, ab_t, ab_prev, act = _mixed_rows(Bs)
    out = cfg_ops.cfg_update_mixed(x, ec, eu, mode, s, ab_t, ab_prev, z,
                                   act, row_offset=off)
    ref = cfg_ref.cfg_update_mixed_windowed(x, ec, eu, mode, s, ab_t,
                                            ab_prev, z, act, row_offset=off)
    assert out.shape == shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-5
    w = slice(off, off + B)
    sliced = cfg_ops.cfg_update_mixed(x, ec, eu, mode[w], s[w], ab_t[w],
                                      ab_prev[w], z, act[w])
    assert jnp.array_equal(out, sliced)


def test_cfg_fuse_mixed_offset_out_of_range_refuses(rng_key):
    """A window past the mixed scalar table must refuse loudly — a row
    reading another row's (mode, ᾱ, s) corrupts a whole trajectory."""
    ks = jax.random.split(rng_key, 4)
    x, ec, eu, z = (jax.random.normal(k, (4, 8, 8, 3)) for k in ks)
    v = jnp.linspace(0.1, 0.9, 6)
    m = jnp.zeros((6,))
    with pytest.raises(ValueError, match="out of range"):
        cfg_ops.cfg_update_mixed(x, ec, eu, m, v, v, v, z, jnp.ones((6,)),
                                 row_offset=3)
    with pytest.raises(ValueError, match="out of range"):
        cfg_ops.cfg_update_mixed(x, ec, eu, m, v, v, v, z, jnp.ones((6,)),
                                 row_offset=-2)


def test_cfg_fuse_mixed_inactive_rows_frozen(rng_key):
    """active=0 rows pass through bit-unchanged in BOTH modes — retired
    clf rows freeze exactly like retired cfg rows."""
    ks = jax.random.split(rng_key, 4)
    shape = (4, 8, 8, 3)
    x, ec, eu, z = (jax.random.normal(k, shape) for k in ks)
    mode = jnp.array([0.0, 1.0, 0.0, 1.0])
    act = jnp.array([1.0, 1.0, 0.0, 0.0])
    _, s, ab_t, ab_prev, _ = _mixed_rows(4)
    out = cfg_ops.cfg_update_mixed(x, ec, eu, mode, s, ab_t, ab_prev, z, act)
    for b, a in enumerate([1, 1, 0, 0]):
        if a:
            assert not jnp.array_equal(out[b], x[b])
        else:
            assert jnp.array_equal(out[b], x[b])


# ---------------------------------------------------------------------------
# non-causal S = n_tok + 1 (the DiT's prepended conditioning token)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,Hq,Hkv", [
    (65, 4, 4),     # 8x8 patch grid + cond token
    (65, 4, 2),     # ...with GQA
    (17, 4, 4),     # 4x4 patch grid + cond token
])
def test_flash_attention_noncausal_token_plus_one(rng_key, S, Hq, Hkv):
    """Encoder-mode attention at the DiT's odd sequence length: S=n_tok+1
    rounds the blocks up to the sublane multiple, so this shape MUST take
    the pad_q/pad_k path (padded K rows masked via true_sk).  Covers GQA
    and the softcap=0 branch explicitly."""
    blk = min(128, max(8, -(-S // 8) * 8))
    assert (-S) % blk, "shape no longer exercises the padding path"
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (2, S, Hq, 32))
    k = jax.random.normal(ks[1], (2, S, Hkv, 32))
    v = jax.random.normal(ks[2], (2, S, Hkv, 32))
    out = fa_ops.flash_attention(q, k, v, causal=False, softcap=0.0)
    ref = fa_ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), causal=False,
                           softcap=0.0).transpose(0, 2, 1, 3)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


# ---------------------------------------------------------------------------
# fused adaLN LayerNorm (kernels/adaln_norm)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,dt", [
    ((2, 17, 128), jnp.float32),    # DiT wave: n_tok+1 (forces row pad)
    ((4, 65, 64), jnp.float32),
    ((3, 256, 128), jnp.float32),   # no padding needed
    ((2, 17, 128), jnp.bfloat16),
])
def test_adaln_norm_matches_oracle(rng_key, shape, dt):
    from repro.kernels.adaln_norm import ops as an_ops
    from repro.kernels.adaln_norm import ref as an_ref
    ks = jax.random.split(rng_key, 3)
    B, _, d = shape
    x = jax.random.normal(ks[0], shape, dt)
    scale = jax.random.normal(ks[1], (B, d), dt) * 0.5
    shift = jax.random.normal(ks[2], (B, d), dt) * 0.5
    out = an_ops.adaln_norm(x, scale, shift)
    assert out.dtype == dt
    ref = an_ref.adaln_norm(x, scale, shift)
    if dt == jnp.bfloat16:
        err = jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))
        assert bool(jnp.all(err <= 2.0 ** -8 *
                            jnp.maximum(jnp.abs(ref.astype(jnp.float32)),
                                        1.0)))
    else:
        assert jnp.max(jnp.abs(out - ref)) < 2e-5


def test_adaln_norm_matches_dit_inline_site(rng_key):
    """The kernel must reproduce the DiT's hand-rolled modulation site
    ``_ln(x)·(1+scale)+shift`` — the expression it replaces."""
    from repro.diffusion.dit import _ln
    from repro.kernels.adaln_norm import ops as an_ops
    ks = jax.random.split(rng_key, 3)
    x = jax.random.normal(ks[0], (3, 17, 64))
    scale = jax.random.normal(ks[1], (3, 64)) * 0.1
    shift = jax.random.normal(ks[2], (3, 64)) * 0.1
    inline = _ln(x) * (1 + scale[:, None]) + shift[:, None]
    out = an_ops.adaln_norm(x, scale, shift)
    assert jnp.max(jnp.abs(out - inline)) < 2e-6


def test_adaln_norm_per_row_modulation(rng_key):
    """Each batch row is modulated by ITS OWN (scale, shift): permuting
    the modulation rows must permute the outputs identically."""
    from repro.kernels.adaln_norm import ops as an_ops
    ks = jax.random.split(rng_key, 3)
    x = jax.random.normal(ks[0], (1, 24, 32))
    x3 = jnp.broadcast_to(x, (3, 24, 32))
    scale = jax.random.normal(ks[1], (3, 32))
    shift = jax.random.normal(ks[2], (3, 32))
    out = an_ops.adaln_norm(x3, scale, shift)
    perm = jnp.array([2, 0, 1])
    out_p = an_ops.adaln_norm(x3, scale[perm], shift[perm])
    assert jnp.array_equal(out[perm], out_p)


# ---------------------------------------------------------------------------
# DiT attention core (kernels/dit_attention)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,heads,hd", [
    (2, 257, 12, 64),   # DiT-B/2: 16x16 patch grid + cond token
    (2, 65, 4, 36),     # 8x8 patch grid + cond token
    (3, 17, 4, 36),     # the paper preset's 4x4 grid + cond token
])
def test_dit_attention_matches_einsum_chain(rng_key, B, S, heads, hd):
    """Forward: the kernel against the chain on the same bfloat16-rounded
    QKV product; what is left is p's bfloat16 cast before the PV product
    (2^-9 relative), so the gap is at most 2^-9·max|v|, here allowed
    twice that.  Backward: the custom VJP is the chain's VJP, so the
    gradients with respect to the (B, S, 3d) input are equal."""
    ks = jax.random.split(rng_key, 2)
    qkv = jax.random.normal(ks[0], (B, S, 3 * heads * hd))
    out = jax.jit(da_ops.dit_attention, static_argnums=1)(qkv, heads)
    assert out.shape == (B, S, heads * hd) and out.dtype == jnp.float32
    rounded = qkv.astype(jnp.bfloat16).astype(jnp.float32)
    ref = da_ref.dit_attention(rounded, heads)
    assert jnp.max(jnp.abs(out - ref)) <= 2.0 ** -8 * jnp.max(jnp.abs(qkv))

    w = jax.random.normal(ks[1], out.shape)
    grads = [jax.jit(jax.grad(lambda x, f=f: jnp.sum(f(x, heads) * w)))(qkv)
             for f in (da_ops.dit_attention, da_ref.dit_attention)]
    assert jnp.array_equal(*grads)


@pytest.mark.parametrize("S,precision,engaged", [
    (17, None, False), (127, None, False), (128, None, True),
    (257, None, True), (da_ops.MAX_SEQ, None, True),
    (da_ops.MAX_SEQ + 1, None, False), (257, "highest", False)])
def test_dit_attention_dispatch_rule(monkeypatch, S, precision, engaged):
    """The default DiT path takes the kernel on the TPU within
    [MIN_SEQ, MAX_SEQ] at the default matmul precision, and never on the
    CPU."""
    assert not da_ops.engages(S)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision(precision):
        assert da_ops.engages(S) == engaged
