"""The benchmark's yardstick: FLOPs and bytes from shapes, and peaks."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_util import ROOT

from benchmarks.chip import cost
from repro.configs.oscar import DiffusionConfig
from repro.diffusion.dit import dit_apply, init_dit


def _spec(d, L, h, lat):
    return dict(d_model=d, num_layers=L, num_heads=h, patch=2,
                latent_size=lat, channels=4, cond_dim=512, mlp_ratio=4,
                dtype="float32")


def _abstract(spec, B):
    dc = DiffusionConfig(d_model=spec["d_model"],
                         num_layers=spec["num_layers"],
                         num_heads=spec["num_heads"], patch=spec["patch"])
    lat = spec["latent_size"]
    params = jax.eval_shape(lambda k: init_dit(k, dc, lat, 4),
                            jax.random.PRNGKey(0))
    args = (jax.ShapeDtypeStruct((B, lat, lat, 4), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, 512), jnp.float32))
    return dc, params, args


def test_forward_flops_match_xla_cost_analysis():
    """Matmuls are all but ~1% of what XLA counts for the forward; the
    rest is elementwise work cost.py leaves out."""
    spec = _spec(256, 3, 4, 32)
    dc, params, args = _abstract(spec, 4)
    xla = jax.jit(lambda p, x, t, y: dit_apply(p, dc, x, t, y)).lower(
        params, *args).cost_analysis()["flops"]
    ratio = xla / cost.forward_flops(spec, 4)
    assert 1.0 <= ratio < 1.05, ratio


def test_published_sizes():
    """DiT-B/2 and DiT-XL/2: parameters as init_dit lays them out, and
    forward FLOPs twice the paper's 23.0 and 118.6 GMACs (plus the
    conditioning token)."""
    for (d, L, h), gmacs in (((768, 12, 12), 23.0), ((1152, 28, 16), 118.6)):
        spec = _spec(d, L, h, 32)
        _, params, _ = _abstract(spec, 1)
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert cost.param_count(spec) == n
        assert abs(cost.forward_flops(spec) / (2e9 * gmacs) - 1) < 0.01


@pytest.mark.parametrize("use_pallas", [False, True])
def test_bytes_are_the_call_signature_whatever_the_path(use_pallas):
    """A call's bytes are its weights, inputs and output, on the plain and
    the fused path alike."""
    spec = _spec(64, 2, 2, 8)
    dc, params, args = _abstract(spec, 3)
    out = jax.eval_shape(lambda p, x, t, y: dit_apply(
        p, dc, x, t, y, use_pallas=use_pallas), params, *args)
    sig = sum(int(np.prod(a.shape)) * 4
              for a in jax.tree.leaves((params, args, out)))
    assert cost.forward_bytes(spec, 3) == sig


def test_dense_roofline_is_bounded_by_each_product():
    spec = _spec(768, 12, 12, 32)
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    dense = [m for m in cost.matmuls(spec, 240) if not cost.is_attention(m[0])]
    t = cost.dense_matmul_roofline_s(spec, 240, peak)
    flops = sum(2.0 * M * N * K for _, M, N, K in dense)
    assert t >= flops / peak["flops_per_s"]
    assert len(dense) == 5 + 5 * 12 + 2


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        cost.peaks("TPU v99")
    table = json.loads((ROOT / "benchmarks" / "chip" /
                        "peaks.json").read_text())
    assert "Google Cloud" in table["source"]
    assert cost.peaks("TPU v5 lite")["flops_per_s"] == 197e12
