"""Mixture-of-Experts FFN with expert parallelism.

Two execution paths, numerically equivalent (up to capacity drops):

* ``dense``     — every expert computed for every selected token via a
                  static loop; used on CPU for tiny smoke tests and as the
                  oracle for the EP path.
* ``ep``        — production path: ``jax.shard_map`` manual only over the
                  ``model`` mesh axis.  Experts are sharded over ``model``;
                  activations stay replicated across ``model`` (Megatron-TP
                  convention), so dispatch is a *local* capacity-gather per
                  expert shard followed by a single ``psum`` combine — the
                  same collective cost as a TP FFN, no all-to-all needed.
                  (See DESIGN.md §4; EXPERIMENTS.md §Perf evaluates a
                  reduce-scatter variant.)

Routing: softmax router, top-k, renormalised gates (Mixtral convention —
noted in DESIGN.md as a simplification for phi3.5's sparsemixer), plus the
standard switch-transformer load-balance auxiliary loss.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import lecun_init
from repro.utils import cdiv


@dataclass(frozen=True)
class Parallel:
    """How the model is laid out on the mesh (None axes = not sharded)."""
    model_axis: Optional[str] = None   # tensor/expert-parallel axis name
    data_axes: tuple = ()              # batch axes ("pod","data")
    mesh: object = None                # jax Mesh (static, not traced)
    use_pallas: bool = False           # route hot paths through Pallas kernels
    moe_combine: str = "psum"          # psum | reduce_scatter  (§Perf knob)
    batch_sharded: bool = True         # False when global_batch < data shards
    resid_spec: object = None          # PartitionSpec pinned on the residual
                                       # stream between groups (§Perf: Megatron
                                       # sequence parallelism)
    logits_spec: object = None         # PartitionSpec pinned on the LM logits
                                       # (vocab-parallel loss; avoids a full
                                       # (B,S,V) f32 materialisation)
    attn_impl: str = "naive"           # naive | chunked  (§Perf knob: the
                                       # chunked path never materialises the
                                       # (B,H,S,S) probability tensor)
    prefill_last_only: bool = False    # serving: readout last position only
    qkv_spec: object = None            # (q_sharding, kv_sharding) pinned on
                                       # the projected q/k/v — stops GSPMD
                                       # from sharding the KV sequence dim
                                       # (which costs probs-sized all-reduces)
    gqa_repeat: bool = False           # materialise repeated KV heads so the
                                       # head dim shards cleanly (§Perf)
    decode_cache: str = "scan_ys"      # scan_ys | carry — cache plumbing for
                                       # decode.  "carry" (in-place DUS into
                                       # the scan carry) was REFUTED on XLA:
                                       # the carry fails to alias and copies
                                       # the full cache per group (§Perf log)

    @property
    def model_size(self) -> int:
        if self.mesh is None or self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, fe, E = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 4)
    p = {
        "w_router": lecun_init(ks[0], (d, E)),
        "experts_up": lecun_init(ks[1], (E, d, fe)),
        "experts_down": lecun_init(ks[2], (E, fe, d), fan_in_axes=(1,)),
    }
    if cfg.gated_mlp:
        p["experts_gate"] = lecun_init(ks[3], (E, d, fe))
    return p


def _route(w_router, x_flat, m: MoEConfig):
    """Returns (gates (T,k), idx (T,k), aux_loss scalar)."""
    logits = (x_flat @ w_router.astype(x_flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gates, idx = jax.lax.top_k(probs, m.top_k)                  # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    T = x_flat.shape[0]
    one_hot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)  # (T,k,E)
    f = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)              # dispatch frac
    pmean = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(f * pmean)
    return gates, idx, aux


def _expert_ffn(xe, up, down, gate, act: str):
    actfn = jax.nn.silu if act == "silu" else (lambda v: jax.nn.gelu(v, approximate=True))
    h = xe @ up.astype(xe.dtype)
    if gate is not None:
        h = actfn(xe @ gate.astype(xe.dtype)) * h
    else:
        h = actfn(h)
    return h @ down.astype(xe.dtype)


def _local_expert_pass(params, cfg: ModelConfig, x_flat, e_start: int, E_loc: int,
                       capacity: int, gates, idx):
    """Gather→FFN→scatter for ``E_loc`` experts starting at global id
    ``e_start``.  Works on local (sharded) or global (dense) expert slabs —
    ``params`` expert arrays must have leading dim ``E_loc``."""
    m = cfg.moe
    T = x_flat.shape[0]
    # Pad x with a zero row; out-of-range gather indices point at it.
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, x_flat.shape[1]), x_flat.dtype)], 0)
    out = jnp.zeros((T, cfg.d_model), x_flat.dtype)
    for e_loc in range(E_loc):
        g = e_start + e_loc
        w_t = jnp.sum(jnp.where(idx == g, gates, 0.0), axis=-1)       # (T,)
        sel = w_t > 0
        # capacity-limited token indices for this expert (fill -> padded row)
        tok = jnp.nonzero(sel, size=capacity, fill_value=T)[0]        # (C,)
        xe = x_pad[tok]                                               # (C, d)
        gate_w = params.get("experts_gate")
        h = _expert_ffn(xe, params["experts_up"][e_loc],
                        params["experts_down"][e_loc],
                        None if gate_w is None else gate_w[e_loc],
                        cfg.mlp_act)
        h = h * w_t[tok][:, None].astype(h.dtype)
        out = out.at[tok].add(h, mode="drop")
    return out


def moe_dense(params, cfg: ModelConfig, x):
    """Single-device reference path (all experts local)."""
    m = cfg.moe
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    gates, idx, aux = _route(params["w_router"], x_flat, m)
    T = x_flat.shape[0]
    capacity = max(1, cdiv(T * m.top_k, m.num_experts) * 4)  # generous: no drops
    out = _local_expert_pass(params, cfg, x_flat, 0, m.num_experts,
                             capacity, gates, idx)
    return out.reshape(B, S, d), aux


def moe_ep(params, cfg: ModelConfig, x, par: Parallel, batch_sharded: bool = True):
    """Expert-parallel path: fully-manual shard_map over all mesh axes.

    Experts shard over ``model``; tokens shard over the data axes (or are
    replicated when the batch is unshardable, e.g. batch=1 decode).  The
    only combine collective is a psum (or reduce-scatter + all-gather,
    §Perf knob) over ``model``.
    """
    m = cfg.moe
    M = par.model_size
    E_loc = m.num_experts // M
    d = cfg.d_model
    gated = "experts_gate" in params
    all_axes = set(par.mesh.axis_names)
    x_spec = P(par.data_axes) if (batch_sharded and par.data_axes) else P()

    def body(*args):
        w_router, e_up, e_down = args[:3]
        e_gate = args[3] if gated else None
        x_loc = args[-1]
        Bl, Sl, _ = x_loc.shape
        x_flat = x_loc.reshape(Bl * Sl, d)
        gates, idx, aux = _route(w_router, x_flat, m)
        T = x_flat.shape[0]
        capacity = max(1, int(T * m.top_k / m.num_experts * m.capacity_factor))
        e_start = jax.lax.axis_index(par.model_axis) * E_loc
        p_loc = {"experts_up": e_up, "experts_down": e_down}
        if gated:
            p_loc["experts_gate"] = e_gate
        out = _local_expert_pass(p_loc, cfg, x_flat, e_start, E_loc,
                                 capacity, gates, idx)
        if par.moe_combine == "reduce_scatter":
            # reduce-scatter over the token axis, then all-gather: same
            # bytes-on-wire as all-reduce but exposes overlap (§Perf).
            out = jax.lax.psum_scatter(out, par.model_axis, scatter_dimension=0,
                                       tiled=True)
            out = jax.lax.all_gather(out, par.model_axis, axis=0, tiled=True)
        else:
            out = jax.lax.psum(out, par.model_axis)
        if par.data_axes:
            aux = jax.lax.pmean(aux, par.data_axes)
        return out.reshape(Bl, Sl, d), aux

    args = [params["w_router"], params["experts_up"], params["experts_down"]]
    specs = [P(), P(par.model_axis), P(par.model_axis)]
    if gated:
        args.append(params["experts_gate"])
        specs.append(P(par.model_axis))
    args.append(x)
    specs.append(x_spec)
    # reduce_scatter+all_gather leaves values replicated over `model` but
    # the VMA checker cannot infer that statically — disable the check for
    # that combine mode only.
    check = par.moe_combine != "reduce_scatter"
    fn = jax.shard_map(body, mesh=par.mesh, axis_names=all_axes,
                       in_specs=tuple(specs), out_specs=(x_spec, P()),
                       check_vma=check)
    return fn(*args)


def moe_apply(params, cfg: ModelConfig, x, par: Parallel):
    """Dispatch to the EP or dense path.  Returns (out, aux_loss)."""
    if par.model_axis is not None and par.mesh is not None:
        return moe_ep(params, cfg, x, par, batch_sharded=par.batch_sharded)
    return moe_dense(params, cfg, x)
