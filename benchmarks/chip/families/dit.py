"""The DiT denoiser family as the system under test serves it.

Builds, from a configuration file and the run's seed, what the harness
drives: the denoiser's weights (made on the device in one jitted call)
and the program's ``SynthesisEngine`` behind a ``SynthesisService``.  The
weights are the benchmark's own, in the layout
``repro.diffusion.dit.dit_apply`` reads; the plain reference in
``references/dit.py`` reads the same layout.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.oscar import DiffusionConfig
from repro.diffusion.schedule import make_schedule
from repro.serve import SynthesisEngine, SynthesisService


def diffusion_config(cfg: dict) -> DiffusionConfig:
    m, s = cfg["model"], cfg["sampler"]
    return DiffusionConfig(d_model=m["d_model"], num_layers=m["num_layers"],
                           num_heads=m["num_heads"], patch=m["patch"],
                           cond_dim=m["cond_dim"],
                           train_timesteps=s["train_timesteps"],
                           sample_timesteps=s["steps"],
                           guidance_scale=s["guidance"],
                           schedule=s["schedule"])


def _leaf_shapes(spec: dict) -> dict:
    d, c = spec["d_model"], spec["cond_dim"]
    n = (spec["latent_size"] // spec["patch"]) ** 2
    pd = spec["patch"] ** 2 * spec["channels"]
    ff = spec["mlp_ratio"] * d
    lin = lambda i, o, b=True: ({"w": (i, o), "b": (o,)} if b
                                else {"w": (i, o)})
    return {"patch_in": lin(pd, d), "pos": (n, d), "t_mlp1": lin(d, d),
            "t_mlp2": lin(d, d), "y_proj": lin(c, d), "null_y": (c,),
            "out_mod": lin(d, 2 * d), "patch_out": lin(d, pd),
            "cond_tok": lin(c, d),
            "blocks": [{"wqkv": lin(d, 3 * d, False), "wo": lin(d, d, False),
                        "w_up": lin(d, ff), "w_down": lin(ff, d),
                        "mod": lin(d, 6 * d)}
                       for _ in range(spec["num_layers"])]}


def _mix(x):
    """MurmurHash3's 32-bit finaliser: a bijection of uint32 that spreads
    every input bit over the output."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _unit_uniform(seed, leaf: int, shape) -> jnp.ndarray:
    """Zero-mean, unit-variance uniform values for one leaf, hashed from
    (seed, leaf, element index): a counter-based generator that is plain
    elementwise integer work on the device.  Never exactly 0."""
    n = math.prod(shape)
    salt = _mix(seed ^ _mix(jnp.uint32(leaf + 1) * jnp.uint32(0x9E3779B9)))
    bits = _mix(jax.lax.iota(jnp.uint32, n) * jnp.uint32(0x9E3779B9) ^ salt)
    u = (bits >> 8).astype(jnp.float32) * 2.0 ** -24 - 0.5 + 2.0 ** -25
    return (u * math.sqrt(12.0)).reshape(shape)


@partial(jax.jit, static_argnames=("spec_items",))
def _make_params(seed, *, spec_items):
    """Every leaf drawn from the seed, none left at zero: matrices at
    1/√fan_in (so the adaLN-zero gates, modulations and output head all
    act), biases and positions at 0.02, and the null conditioning Ø on
    the unit sphere like an encoding."""
    spec = dict(spec_items)
    dtype = jnp.dtype(spec["dtype"])
    shapes = _leaf_shapes(spec)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda v: isinstance(
        v, tuple))
    out = []
    for i, shape in enumerate(flat):
        z = _unit_uniform(seed, i, shape)
        scale = 1.0 / math.sqrt(shape[0]) if len(shape) == 2 else 0.02
        out.append((z * scale).astype(dtype))
    params = jax.tree.unflatten(tree, out)
    null = _unit_uniform(seed, len(flat), (spec["cond_dim"],))
    params["null_y"] = (null / jnp.linalg.norm(null)).astype(dtype)
    return params


def make_params(spec: dict, seed: int):
    """The weights of ``seed`` (a whole number below 2**32)."""
    return _make_params(jnp.uint32(seed),
                        spec_items=tuple(sorted(spec.items())))


class System:
    """One configuration's system under test for one run, on one chip."""

    def __init__(self, cfg: dict, seed: int, *, chips: int = 1, clock=None):
        if chips != 1:
            raise ValueError("the DiT family serves from one chip; a cell on "
                             "more chips needs its own placement here")
        self.cfg = cfg
        self.spec = cfg["model"]
        self.dc = diffusion_config(cfg)
        self.sched = make_schedule(self.dc.train_timesteps, self.dc.schedule)
        self.phases = {}
        t = clock() if clock else 0.0
        self.params = make_params(self.spec, seed)
        jax.block_until_ready(self.params)
        if clock:
            self.phases["weights"] = clock() - t
            t = clock()
        e = cfg["engine"]
        self.engine = SynthesisEngine(
            self.params, self.dc, self.sched,
            image_size=self.spec["latent_size"],
            channels=self.spec["channels"], wave_size=e["wave_size"],
            granule=e["granule"], use_pallas=e["use_pallas"])
        if clock:
            self.phases["engine"] = clock() - t
        self.wave_size = self.engine.wave_size
        self.granule = self.engine.granule
        self.ragged = e["ragged"]
        self.compaction = e.get("compaction")

    def service(self, service_cls=SynthesisService):
        return service_cls(self.engine, ragged=self.ragged,
                           compaction=self.compaction)

    def free(self):
        """Drop the engine and its caches; the weights stay for the
        reference."""
        self.engine = None
