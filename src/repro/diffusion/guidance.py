"""Guidance strategies + the single reverse-process core.

Every sampler in the repo (classifier-free — paper Eq. 8/9, classifier-
guided — Eq. 4 / FedCADO, and unconditional) is the SAME ancestral/DDIM
loop differing only in how the per-step score ε̂ is produced.  That
difference is factored into a ``GuidanceStrategy``; ``reverse_sample`` owns
the respacing, the scan loop, the per-step noise draw, and the fused
guidance-combine + ancestral update (Pallas ``kernels/cfg_fuse`` when
enabled).

A strategy answers two questions per step:

* ``eps(params, dc, x, t, ab_t, aux) -> (eps_c, eps_u, s)`` — the pair of
  score evaluations fed to the fused update ``(1+s)·ε_c − s·ε_u``.  A
  strategy whose guidance is already folded into a single ε̂ (classifier-
  guided, unconditional) returns ``eps_u=None`` and the core applies the
  plain ancestral step — bit-identical to the historical samplers.
* ``prepare(params, dc) -> aux`` — per-trajectory precompute hoisted out
  of the scan (e.g. the stacked cond/uncond conditioning batch).

Every scan body runs under the ``sampler.step`` named scope: in a
profiler trace the guidance combine, noise draw and update sit there,
and the denoiser's ops under its own ``dit.*`` scopes inside it.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.oscar import DiffusionConfig
from repro.diffusion.dit import dit_apply
from repro.diffusion.schedule import NoiseSchedule


SAMPLER_SCOPE = "sampler.step"


def _scan_body(step):
    """``step`` under the ``sampler.step`` named scope (op metadata
    only)."""
    @functools.wraps(step)
    def body(*args):
        with jax.named_scope(SAMPLER_SCOPE):
            return step(*args)
    return body


def _strictly_decreasing(ts, num_steps: int):
    """Enforce a strictly-decreasing integer trajectory ending at 0.

    Rounding the respaced linspace can emit repeated t values (certain
    when ``num_steps > T``; a float-precision hazard near it), and a
    repeated timestep is a wasted denoiser call: ᾱ_t == ᾱ_prev makes the
    update pure re-noising.  The fix is the tightest strictly-decreasing
    envelope under the rounded trajectory (``cummin`` of ``ts + i`` minus
    ``i``), floored so the tail still reaches 0 — the identity whenever
    the input is already strictly decreasing, which is every collision-
    free case, so historical trajectories are reproduced bit-exactly.
    """
    i = jnp.arange(num_steps)
    ts = jax.lax.cummin(ts + i) - i            # strictly decreasing
    return jnp.maximum(ts, num_steps - 1 - i)  # …and still ends at 0


def respaced_ts(T: int, num_steps: int):
    if num_steps > T:
        raise ValueError(
            f"num_steps={num_steps} > T={T}: a respaced trajectory cannot "
            f"visit more distinct timesteps than the schedule has")
    ts = jnp.linspace(T - 1, 0, num_steps).round().astype(jnp.int32)
    return _strictly_decreasing(ts, num_steps)


def ancestral_coeffs(sched: NoiseSchedule, ts):
    """Per-step (ᾱ_t, ᾱ_prev) for the respaced trajectory."""
    ab_t = sched.alpha_bar[ts]
    ab_prev = jnp.concatenate([sched.alpha_bar[ts[1:]], jnp.ones((1,))])
    return ab_t, ab_prev


def _cfg_update(x, eps_c, eps_u, s, ab_t, ab_prev, noise, eta, use_pallas):
    if use_pallas:
        from repro.kernels.cfg_fuse import ops as cfg_ops
        return cfg_ops.cfg_update(x, eps_c, eps_u, s, ab_t, ab_prev, noise, eta)
    from repro.kernels.cfg_fuse import ref as cfg_ref
    return cfg_ref.cfg_update(x, eps_c, eps_u, s, ab_t, ab_prev, noise, eta)


class GuidanceStrategy:
    """How one reverse step turns x_t into the guided score pair."""

    def batch(self) -> int:
        raise NotImplementedError

    def prepare(self, params, dc: DiffusionConfig):
        return None

    def eps(self, params, dc: DiffusionConfig, x, t, ab_t, aux,
            use_pallas: bool = False):
        raise NotImplementedError


@dataclass(frozen=True)
class ClassifierFree(GuidanceStrategy):
    """Paper Eq. 8: ε̂ = (1+s)·ε_θ(x,t,ȳ) − s·ε_θ(x,t,Ø), both score
    evaluations batched into ONE denoiser call (cond/uncond stacked on
    batch — DESIGN.md §4)."""
    y: Any                      # (B, cond_dim) encodings ȳ
    scale: float

    def batch(self) -> int:
        return self.y.shape[0]

    def prepare(self, params, dc):
        B = self.y.shape[0]
        null = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
        return jnp.concatenate([self.y, null], axis=0)

    def eps(self, params, dc, x, t, ab_t, y2, use_pallas=False):
        B = x.shape[0]
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.full((2 * B,), t, jnp.int32)
        eps2 = dit_apply(params, dc, x2, t2, y2, use_pallas=use_pallas)
        return eps2[:B], eps2[B:], self.scale


@dataclass(frozen=True)
class ClassifierGuided(GuidanceStrategy):
    """Paper Eq. 4 (FedCADO): unconditional score steered by the gradient
    of a client classifier's log p(y|x)."""
    logprob_fn: Callable        # (x, labels) -> (B,) log p(y|x)
    labels: Any                 # (B,) int32
    scale: float

    def batch(self) -> int:
        return self.labels.shape[0]

    def eps(self, params, dc, x, t, ab_t, aux, use_pallas=False):
        B = x.shape[0]
        tb = jnp.full((B,), t, jnp.int32)
        eps_u = dit_apply(params, dc, x, tb, None,      # unconditional score
                          use_pallas=use_pallas)
        sigma_t = jnp.sqrt(1.0 - ab_t)

        # classifier gradient taken at the x̂₀ prediction; the ∂x̂₀/∂x_t
        # chain factor 1/√ᾱ_t diverges at early steps (ᾱ→0) and destroys
        # samples, so the standard stabilisation is ∇_{x̂₀} directly with
        # per-sample normalisation (gradient direction, ε-scale magnitude).
        x0 = jnp.clip((x - jnp.sqrt(1 - ab_t) * eps_u) / jnp.sqrt(ab_t), -1, 1)
        labels = self.labels
        grad = jax.grad(lambda z: jnp.sum(self.logprob_fn(z, labels)))(x0)
        gnorm = jnp.sqrt(jnp.sum(grad ** 2, axis=(1, 2, 3), keepdims=True))
        grad = grad / jnp.maximum(gnorm, 1e-6)
        enorm = jnp.sqrt(jnp.mean(eps_u ** 2, axis=(1, 2, 3), keepdims=True))
        eps_hat = eps_u - self.scale * sigma_t * grad * enorm  # Eq. 4 (stab.)
        return eps_hat, None, 0.0


@dataclass(frozen=True)
class Unconditional(GuidanceStrategy):
    """Plain p(x) sampling through the null embedding Ø — the degenerate
    guidance point (FedDISC-style generation without a steering signal)."""
    num: int

    def batch(self) -> int:
        return self.num

    def eps(self, params, dc, x, t, ab_t, aux, use_pallas=False):
        B = x.shape[0]
        tb = jnp.full((B,), t, jnp.int32)
        return (dit_apply(params, dc, x, tb, None, use_pallas=use_pallas),
                None, 0.0)


def reverse_sample(params, dc: DiffusionConfig, sched: NoiseSchedule,
                   strategy: GuidanceStrategy, key, *,
                   image_size: int | None = None, channels: int = 3,
                   num_steps: int | None = None, eta: float = 1.0,
                   use_pallas: bool = False):
    """The one ancestral/DDIM loop (paper Eq. 9) shared by every strategy.

    x_T ~ N(0,I); for t in the respaced schedule the strategy produces the
    guided score pair and the fused update advances x_t → x_{t−1}.
    """
    B = strategy.batch()
    H = image_size or 16
    num_steps = num_steps or dc.sample_timesteps
    ts = respaced_ts(sched.T, num_steps)
    ab_t, ab_prev = ancestral_coeffs(sched, ts)

    key, k0 = jax.random.split(key)
    x = jax.random.normal(k0, (B, H, H, channels))
    aux = strategy.prepare(params, dc)

    @_scan_body
    def step(carry, inp):
        x, key = carry
        t, abt, abp = inp
        key, kn = jax.random.split(key)
        eps_c, eps_u, s = strategy.eps(params, dc, x, t, abt, aux,
                                       use_pallas=use_pallas)
        noise = jax.random.normal(kn, x.shape) * (t > 0)
        if eps_u is None:
            from repro.kernels.cfg_fuse import ref as cfg_ref
            x = cfg_ref.ancestral_step(x, eps_c, abt, abp, noise, eta)
        else:
            x = _cfg_update(x, eps_c, eps_u, s, abt, abp, noise, eta,
                            use_pallas)
        return (x, key), None

    (x, _), _ = jax.lax.scan(step, (x, key), (ts, ab_t, ab_prev))
    return jnp.clip(x, -1.0, 1.0)


# ---------------------------------------------------------------------------
# ragged mode: per-row (guidance, steps) inside ONE compiled trajectory
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=512)
def _respaced_ts_host(T: int, k: int) -> np.ndarray:
    """Host-side memo of ``respaced_ts``: the (T, k) → trajectory mapping
    never changes, and table building runs in the packer's double-buffered
    window — a device dispatch + sync per wave there would eat the overlap
    the engine buys with async dispatch."""
    return np.asarray(respaced_ts(T, k), np.int32)


def ragged_tables(sched: NoiseSchedule, steps, max_steps: int):
    """Right-aligned per-row respacing tables for a ragged wave.

    Row ``b`` with ``steps[b] = k`` runs its k-step trajectory over the
    LAST k of ``max_steps`` scan iterations — every row finishes on the
    same final iteration, so the terminal clip stays shared — and is
    frozen before that by the active mask.  Each row's table slice is the
    row's own ``respaced_ts``/``ancestral_coeffs`` verbatim (built host-
    side per distinct step count), which is what makes a ragged row
    bit-exact against the same row sampled in a uniform wave.

    Returns ``(ts, ab_t, ab_prev, jloc)`` as (B, max_steps) numpy arrays;
    ``jloc[b, i] = i - (max_steps - k)`` is the row-local step index,
    negative while the row is frozen (``jloc >= 0`` is the active mask,
    and it keys the row's per-step noise stream so alignment padding
    never shifts a row's draws).  Frozen slots carry the row's first real
    (t, ᾱ) values — valid schedule positions, so the masked-out update
    lanes stay finite.
    """
    steps = np.asarray(steps, np.int32).reshape(-1)
    B, S = len(steps), int(max_steps)
    if steps.max(initial=1) > S:
        raise ValueError(f"max_steps={S} < largest row step count "
                         f"{int(steps.max())}")
    alpha_bar = np.asarray(sched.alpha_bar, np.float32)
    ts = np.zeros((B, S), np.int32)
    ab_t = np.zeros((B, S), np.float32)
    ab_prev = np.zeros((B, S), np.float32)
    jloc = np.arange(S, dtype=np.int32)[None] - (S - steps)[:, None]
    for k in np.unique(steps):
        rows = steps == k
        ts_k = _respaced_ts_host(sched.T, int(k))
        ab_k = alpha_bar[ts_k]
        abp_k = np.concatenate([ab_k[1:], np.ones((1,), np.float32)])
        ts[rows] = np.concatenate([np.full(S - k, ts_k[0], np.int32), ts_k])
        ab_t[rows] = np.concatenate([np.full(S - k, ab_k[0], np.float32),
                                     ab_k])
        ab_prev[rows] = np.concatenate([np.full(S - k, abp_k[0], np.float32),
                                        abp_k])
    return ts, ab_t, ab_prev, jloc


def _cfg_update_rowwise(x, eps_c, eps_u, s, ab_t, ab_prev, noise, active,
                        eta, use_pallas):
    if use_pallas:
        from repro.kernels.cfg_fuse import ops as cfg_ops
        return cfg_ops.cfg_update_rowwise(x, eps_c, eps_u, s, ab_t, ab_prev,
                                          noise, active, eta)
    from repro.kernels.cfg_fuse import ref as cfg_ref
    return cfg_ref.cfg_update_rowwise(x, eps_c, eps_u, s, ab_t, ab_prev,
                                      noise, active, eta)


def _ragged_scan(params, dc: DiffusionConfig, x, y2, row_keys, guidance,
                 ts, ab_t, ab_prev, jloc, *, eta: float, use_pallas: bool):
    """The shared per-row reverse scan: one iteration per table column,
    per-row (t, ᾱ_t, ᾱ_prev, guidance), per-row noise keyed
    ``fold_in(row_keys[b], 1 + j)`` with j the row-LOCAL step index, and
    an active mask (``jloc >= 0``) freezing rows whose right-aligned
    trajectory has not started.  Both the one-shot ragged wave and every
    compaction segment run THIS body, so their arithmetic is identical by
    construction — the substrate of the compacted/ragged bit-parity.
    Returns the advanced x UNCLIPPED (callers clip once, at the end of the
    full trajectory)."""
    B, H, _, channels = x.shape

    @_scan_body
    def step(x, inp):
        t, abt, abp, j = inp                     # (B,) each
        active = j >= 0
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.concatenate([t, t])
        eps2 = dit_apply(params, dc, x2, t2, y2, use_pallas=use_pallas)
        eps_c, eps_u = eps2[:B], eps2[B:]
        nk = jax.vmap(jax.random.fold_in)(row_keys,
                                          jnp.maximum(j, 0) + 1)
        noise = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(nk)
        noise = noise * (t > 0)[:, None, None, None]
        x = _cfg_update_rowwise(x, eps_c, eps_u, guidance, abt, abp, noise,
                                active, eta, use_pallas)
        return x, None

    x, _ = jax.lax.scan(step, x,
                        (jnp.asarray(ts).T, jnp.asarray(ab_t).T,
                         jnp.asarray(ab_prev).T, jnp.asarray(jloc).T))
    return x


def reverse_sample_ragged(params, dc: DiffusionConfig, y, row_keys, guidance,
                          ts, ab_t, ab_prev, jloc, *, image_size: int,
                          channels: int = 3, eta: float = 1.0,
                          use_pallas: bool = False):
    """Classifier-free reverse loop with PER-ROW (guidance, steps).

    One compiled (B, max_steps) geometry serves rows from different
    classifier-free groups: each row carries its own guidance scale
    (``guidance`` (B,)), its own right-aligned respacing slice of the
    (B, S) tables from ``ragged_tables``, and its OWN noise stream —
    row ``b`` draws x_T from ``fold_in(row_keys[b], 0)`` and step-j noise
    from ``fold_in(row_keys[b], 1 + j)`` with j the row-LOCAL step index.
    Row-keyed noise is what makes the result independent of wave packing:
    a row produces bit-identical output whether its wave holds its own
    group, a mix of groups, or alignment padding.
    """
    B = y.shape[0]
    H = image_size
    kx = jax.vmap(lambda k: jax.random.fold_in(k, 0))(row_keys)
    x = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(kx)
    null = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
    y2 = jnp.concatenate([y, null], axis=0)
    guidance = jnp.asarray(guidance, jnp.float32)
    x = _ragged_scan(params, dc, x, y2, row_keys, guidance,
                     ts, ab_t, ab_prev, jloc, eta=eta, use_pallas=use_pallas)
    return jnp.clip(x, -1.0, 1.0)


# ---------------------------------------------------------------------------
# windowed mode: per-host row windows of a wave-resident scalar table
# ---------------------------------------------------------------------------
#
# Multi-host serving shards one merged wave into contiguous per-host windows
# (serve/topology.py::WavePlacement).  The wave's per-row (ᾱ_t, ᾱ_prev, s,
# active) scalars live in ONE wave-resident table; a host's scan updates
# only its window's rows and reads row b's scalars at wave slot
# ``row_offset + b`` through the segment-offset cfg_fuse path — no per-host
# sliced copy of the table per step.  Because row noise is keyed by request
# identity and the per-row arithmetic is independent across rows, a window
# scan is bit-exact against the same rows inside the full-wave ragged scan.


def _cfg_update_window(x, eps_c, eps_u, s, ab_t, ab_prev, noise, active,
                       row_offset, eta, use_pallas):
    if use_pallas:
        from repro.kernels.cfg_fuse import ops as cfg_ops
        return cfg_ops.cfg_update_rowwise(x, eps_c, eps_u, s, ab_t, ab_prev,
                                          noise, active, eta,
                                          row_offset=row_offset)
    from repro.kernels.cfg_fuse import ref as cfg_ref
    return cfg_ref.cfg_update_rowwise_windowed(x, eps_c, eps_u, s, ab_t,
                                               ab_prev, noise, active,
                                               row_offset=row_offset, eta=eta)


def _ragged_scan_window(params, dc: DiffusionConfig, x, y2, row_keys,
                        guidance, ts, jloc, ab_t, ab_prev, active, *,
                        row_offset: int, eta: float, use_pallas: bool):
    """The windowed per-row reverse scan: ``x`` holds only wave rows
    ``[row_offset, row_offset + Bw)``.  ``guidance`` (B,) and
    ``ab_t``/``ab_prev``/``active`` (B, S) span the FULL wave — the fused
    update reads tensor row b's scalars at wave slot ``row_offset + b``
    (``cfg_update_rowwise(row_offset=...)``) — while ``ts``/``jloc``
    (Bw, S) are window-local (only this window's rows feed the denoiser
    and the noise stream).  Per-row arithmetic is identical to
    ``_ragged_scan``; only which rows this launch updates changes, which
    is the substrate of the cross-topology bit-parity.  Returns x
    UNCLIPPED."""
    B, H, _, channels = x.shape

    @_scan_body
    def step(x, inp):
        t, j, abt, abp, act = inp         # t/j: (Bw,); abt/abp/act: (B,)
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.concatenate([t, t])
        eps2 = dit_apply(params, dc, x2, t2, y2, use_pallas=use_pallas)
        eps_c, eps_u = eps2[:B], eps2[B:]
        nk = jax.vmap(jax.random.fold_in)(row_keys,
                                          jnp.maximum(j, 0) + 1)
        noise = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(nk)
        noise = noise * (t > 0)[:, None, None, None]
        x = _cfg_update_window(x, eps_c, eps_u, guidance, abt, abp, noise,
                               act, row_offset, eta, use_pallas)
        return x, None

    x, _ = jax.lax.scan(step, x,
                        (jnp.asarray(ts).T, jnp.asarray(jloc).T,
                         jnp.asarray(ab_t).T, jnp.asarray(ab_prev).T,
                         jnp.asarray(active).T))
    return x


def reverse_sample_window(params, dc: DiffusionConfig, x, y, row_keys,
                          guidance, ts, jloc, ab_t, ab_prev, active, *,
                          row_offset: int, image_size: int, channels: int = 3,
                          eta: float = 1.0, use_pallas: bool = False):
    """One segment of one host window: advance the carried rows, admit
    the new.  ``x`` is the previous segment's output (the first
    ``x.shape[0]`` rows of this segment); rows ``x.shape[0]:`` activate
    here — their x_T is drawn from ``fold_in(row_keys[b], 0)``, the same
    draw every other schedule makes for that row.  ``y``/``row_keys`` and
    the ``ts``/``jloc`` tables are window-local slices;
    ``guidance``/``ab_t``/``ab_prev``/``active`` span the full wave (see
    ``_ragged_scan_window``).  Returns x UNCLIPPED (the trajectory may
    continue into the next segment; the caller clips once at the end)."""
    n_prev = x.shape[0]
    H = image_size
    kx = jax.vmap(lambda k: jax.random.fold_in(k, 0))(row_keys[n_prev:])
    x_new = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(kx)
    x = jnp.concatenate([x, x_new], axis=0)
    B = x.shape[0]
    null = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
    y2 = jnp.concatenate([y, null], axis=0)
    return _ragged_scan_window(params, dc, x, y2, row_keys,
                               jnp.asarray(guidance, jnp.float32), ts, jloc,
                               ab_t, ab_prev, active, row_offset=row_offset,
                               eta=eta, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# mixed mode: cfg + classifier-guided + uncond rows in ONE ragged wave
# ---------------------------------------------------------------------------
#
# Every guidance strategy is the same ancestral loop differing only in how
# ε̂ is produced, so a wave can carry all three per ROW: ``mode`` (B,)
# selects the combine (0 = cfg pair-combine; uncond rides it as the s=0,
# null-cond degenerate point; 1 = classifier ε̂-correction), ``clf_ids``
# (B,) picks the row's classifier out of the wave's ensemble tuple, and
# ``labels`` (B,) feeds the classifiers.  The classifier correction is
# vectorised by evaluating each ensemble member's gradient over the FULL
# batch and selecting per row — heterogeneous ensembles need no lax.switch
# because the stack/select is itself shape-uniform.  Batching contract:
# a classifier's per-row log p(y|x) must depend only on that row (true for
# any per-sample net; batch-coupled ops like batchnorm would break the
# row-independence that makes packing invisible in D_syn).  Because each
# row's noise is keyed by request identity and all per-row arithmetic is
# row-independent, a mixed wave is bit-exact against the same rows drained
# in isolated single-mode waves — at any H, packing, or arrival order.


def _cfg_update_mixed(x, eps_c, eps_u, mode, s, ab_t, ab_prev, noise, active,
                      eta, use_pallas):
    if use_pallas:
        from repro.kernels.cfg_fuse import ops as cfg_ops
        return cfg_ops.cfg_update_mixed(x, eps_c, eps_u, mode, s, ab_t,
                                        ab_prev, noise, active, eta)
    from repro.kernels.cfg_fuse import ref as cfg_ref
    return cfg_ref.cfg_update_mixed(x, eps_c, eps_u, mode, s, ab_t, ab_prev,
                                    noise, active, eta)


def _cfg_update_mixed_window(x, eps_c, eps_u, mode, s, ab_t, ab_prev, noise,
                             active, row_offset, eta, use_pallas):
    if use_pallas:
        from repro.kernels.cfg_fuse import ops as cfg_ops
        return cfg_ops.cfg_update_mixed(x, eps_c, eps_u, mode, s, ab_t,
                                        ab_prev, noise, active, eta,
                                        row_offset=row_offset)
    from repro.kernels.cfg_fuse import ref as cfg_ref
    return cfg_ref.cfg_update_mixed_windowed(x, eps_c, eps_u, mode, s, ab_t,
                                             ab_prev, noise, active,
                                             row_offset=row_offset, eta=eta)


def _clf_correct(eps_c, eps_u, x, ab_t, scale, labels, clf_ids, clf_fns,
                 is_clf):
    """Row-wise classifier ε̂-correction (Eq. 4) over a mixed wave.

    Replaces ``eps_c`` on classifier rows with the stabilised FedCADO
    update — ∇ log p(y|x̂₀) with per-sample gradient normalisation and
    ε-scale magnitude, line-for-line the arithmetic of
    ``ClassifierGuided.eps`` — leaving every other row's ε_c untouched
    for the cfg combine.  Each ensemble member is evaluated over the
    full batch and rows select their own via ``clf_ids``; a member's
    per-row output depends only on that row (the batching contract), so
    the values match the isolated per-classifier evaluation bit-exactly.
    """
    B = x.shape[0]
    r = lambda v: jnp.asarray(v).reshape((-1,) + (1,) * (x.ndim - 1))
    ab = r(ab_t)
    sigma_t = jnp.sqrt(1.0 - ab)
    x0 = jnp.clip((x - jnp.sqrt(1 - ab) * eps_u) / jnp.sqrt(ab), -1, 1)
    enorm = jnp.sqrt(jnp.mean(eps_u ** 2, axis=(1, 2, 3), keepdims=True))
    hats = []
    for fn in clf_fns:
        grad = jax.grad(lambda z, f=fn: jnp.sum(f(z, labels)))(x0)
        gnorm = jnp.sqrt(jnp.sum(grad ** 2, axis=(1, 2, 3), keepdims=True))
        grad = grad / jnp.maximum(gnorm, 1e-6)
        hats.append(eps_u - r(scale) * sigma_t * grad * enorm)  # Eq. 4
    eps_hat = jnp.stack(hats)[jnp.asarray(clf_ids), jnp.arange(B)]
    return jnp.where(r(is_clf), eps_hat, eps_c)


def _mixed_scan(params, dc: DiffusionConfig, x, y2, row_keys, guidance, mode,
                clf_ids, labels, ts, ab_t, ab_prev, jloc, *, clf_fns,
                eta: float, use_pallas: bool):
    """The mixed-mode sibling of ``_ragged_scan``: same stacked 2B
    denoiser call, same identity-keyed noise stream, same active mask —
    plus the per-row classifier correction and the per-row-mode fused
    update.  Returns x UNCLIPPED."""
    B, H, _, channels = x.shape
    mode = jnp.asarray(mode, jnp.float32)
    is_clf = mode >= 0.5

    @_scan_body
    def step(x, inp):
        t, abt, abp, j = inp                     # (B,) each
        active = j >= 0
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.concatenate([t, t])
        eps2 = dit_apply(params, dc, x2, t2, y2, use_pallas=use_pallas)
        eps_c, eps_u = eps2[:B], eps2[B:]
        if clf_fns:
            eps_c = _clf_correct(eps_c, eps_u, x, abt, guidance, labels,
                                 clf_ids, clf_fns, is_clf)
        nk = jax.vmap(jax.random.fold_in)(row_keys,
                                          jnp.maximum(j, 0) + 1)
        noise = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(nk)
        noise = noise * (t > 0)[:, None, None, None]
        x = _cfg_update_mixed(x, eps_c, eps_u, mode, guidance, abt, abp,
                              noise, active, eta, use_pallas)
        return x, None

    x, _ = jax.lax.scan(step, x,
                        (jnp.asarray(ts).T, jnp.asarray(ab_t).T,
                         jnp.asarray(ab_prev).T, jnp.asarray(jloc).T))
    return x


def reverse_sample_mixed(params, dc: DiffusionConfig, y, row_keys, guidance,
                         mode, clf_ids, labels, ts, ab_t, ab_prev, jloc, *,
                         clf_fns=(), image_size: int, channels: int = 3,
                         eta: float = 1.0, use_pallas: bool = False):
    """Mixed-guidance reverse loop: PER-ROW (mode, guidance, steps).

    ``y`` carries the row's conditioning — the category encoding for cfg
    rows, the null embedding Ø for classifier-guided and uncond rows
    (``dit_apply(y=None)`` broadcasts the same Ø, so the substitution is
    bit-invisible).  Row b draws x_T from ``fold_in(row_keys[b], 0)`` and
    step-j noise from ``fold_in(row_keys[b], 1 + j)`` exactly like the
    pure-cfg ragged wave, so a row's value is independent of which modes
    share its wave."""
    B = y.shape[0]
    H = image_size
    kx = jax.vmap(lambda k: jax.random.fold_in(k, 0))(row_keys)
    x = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(kx)
    null = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
    y2 = jnp.concatenate([y, null], axis=0)
    x = _mixed_scan(params, dc, x, y2, row_keys,
                    jnp.asarray(guidance, jnp.float32), mode, clf_ids,
                    labels, ts, ab_t, ab_prev, jloc, clf_fns=clf_fns,
                    eta=eta, use_pallas=use_pallas)
    return jnp.clip(x, -1.0, 1.0)


def _mixed_scan_window(params, dc: DiffusionConfig, x, y2, row_keys,
                       guidance, mode, clf_ids, labels, ts, jloc, ab_t,
                       ab_prev, active, *, clf_fns, row_offset: int,
                       eta: float, use_pallas: bool):
    """Windowed mixed scan: ``guidance``/``mode``/``ab_t``/``ab_prev``/
    ``active`` span the FULL wave (the fused update reads tensor row b at
    wave slot ``row_offset + b``); ``x``/``y2``/``row_keys``/``labels``/
    ``clf_ids`` and ``ts``/``jloc`` are window-local.  The classifier
    correction needs this window's per-row scalars, so it slices the
    wave-resident ``mode``/``guidance``/``ab_t`` by the (possibly traced)
    ``row_offset``.  Returns x UNCLIPPED."""
    B, H, _, channels = x.shape
    mode = jnp.asarray(mode, jnp.float32)
    guidance = jnp.asarray(guidance, jnp.float32)
    sl = lambda v: jax.lax.dynamic_slice_in_dim(v, row_offset, B, 0)
    is_clf_w = sl(mode) >= 0.5
    g_w = sl(guidance)

    @_scan_body
    def step(x, inp):
        t, j, abt, abp, act = inp         # t/j: (Bw,); abt/abp/act: (B,)
        x2 = jnp.concatenate([x, x], axis=0)
        t2 = jnp.concatenate([t, t])
        eps2 = dit_apply(params, dc, x2, t2, y2, use_pallas=use_pallas)
        eps_c, eps_u = eps2[:B], eps2[B:]
        if clf_fns:
            eps_c = _clf_correct(eps_c, eps_u, x, sl(abt), g_w, labels,
                                 clf_ids, clf_fns, is_clf_w)
        nk = jax.vmap(jax.random.fold_in)(row_keys,
                                          jnp.maximum(j, 0) + 1)
        noise = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(nk)
        noise = noise * (t > 0)[:, None, None, None]
        x = _cfg_update_mixed_window(x, eps_c, eps_u, mode, guidance, abt,
                                     abp, noise, act, row_offset, eta,
                                     use_pallas)
        return x, None

    x, _ = jax.lax.scan(step, x,
                        (jnp.asarray(ts).T, jnp.asarray(jloc).T,
                         jnp.asarray(ab_t).T, jnp.asarray(ab_prev).T,
                         jnp.asarray(active).T))
    return x


def reverse_sample_mixed_window(params, dc: DiffusionConfig, x, y, row_keys,
                                guidance, mode, clf_ids, labels, ts, jloc,
                                ab_t, ab_prev, active, *, clf_fns=(),
                                row_offset: int, image_size: int,
                                channels: int = 3, eta: float = 1.0,
                                use_pallas: bool = False):
    """One segment of one host window of a MIXED wave: advance the
    carried rows, admit the new (x_T from ``fold_in(row_keys[b], 0)``).
    Same window contract as ``reverse_sample_window`` plus the wave-
    resident ``mode`` table and window-local ``clf_ids``/``labels``.
    Returns x UNCLIPPED."""
    n_prev = x.shape[0]
    H = image_size
    kx = jax.vmap(lambda k: jax.random.fold_in(k, 0))(row_keys[n_prev:])
    x_new = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(kx)
    x = jnp.concatenate([x, x_new], axis=0)
    B = x.shape[0]
    null = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
    y2 = jnp.concatenate([y, null], axis=0)
    return _mixed_scan_window(params, dc, x, y2, row_keys,
                              jnp.asarray(guidance, jnp.float32), mode,
                              clf_ids, labels, ts, jloc, ab_t, ab_prev,
                              active, clf_fns=clf_fns, row_offset=row_offset,
                              eta=eta, use_pallas=use_pallas)


def reverse_sample_mixed_segment(params, dc: DiffusionConfig, x, y, row_keys,
                                 guidance, ts, ab_t, ab_prev, jloc, *,
                                 mode, clf_ids, labels, clf_fns=(),
                                 image_size: int, channels: int = 3,
                                 eta: float = 1.0, use_pallas: bool = False):
    """One compaction epoch of a MIXED wave: the mixed sibling of
    ``reverse_sample_segment`` (same admit-then-scan shape, same x_T
    draw), with the per-row mode/classifier operands riding along.
    Returns x UNCLIPPED."""
    n_prev = x.shape[0]
    H = image_size
    kx = jax.vmap(lambda k: jax.random.fold_in(k, 0))(row_keys[n_prev:])
    x_new = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(kx)
    x = jnp.concatenate([x, x_new], axis=0)
    B = x.shape[0]
    null = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
    y2 = jnp.concatenate([y, null], axis=0)
    return _mixed_scan(params, dc, x, y2, row_keys,
                       jnp.asarray(guidance, jnp.float32), mode, clf_ids,
                       labels, ts, ab_t, ab_prev, jloc, clf_fns=clf_fns,
                       eta=eta, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# compacted mode: iteration-compacted nested waves (compute-skipping ragged)
# ---------------------------------------------------------------------------
#
# The one-shot ragged scan runs EVERY row through all max_steps iterations;
# right-aligned rows whose trajectory has not started ride the denoiser
# frozen — pure discarded compute (the row_iters_scheduled vs _active gap).
# Compaction partitions the iteration axis into K ACTIVATION EPOCHS: rows
# are sorted by start iteration (host-side, stable), and each epoch runs
# one scan segment over only the rows live by that epoch's end — nested
# waves whose batch grows as rows activate.  Because every row's noise is
# keyed by its request identity (not wave position or iteration count),
# and a frozen iteration is the identity on x, running a row's trajectory
# in segments is BIT-EXACT vs the one-shot ragged scan.


def plan_epochs(steps, max_steps: int, *, compaction="full",
                granule: int = 1, geoms=None, compile_cost: int = 256):
    """Partition a ragged wave into activation epochs.

    ``steps`` (B,) per-row step counts, ``max_steps`` the wave's step
    ceiling.  Row b activates at iteration ``start = max_steps - steps[b]``
    of the right-aligned shared scan.  Returns ``(order, epochs)``:
    ``order`` (B,) sorts rows by activation (earliest first, stable, so
    the rows live in any epoch are a PREFIX of the sorted order), and
    ``epochs`` is a tuple of ``(rows, begin, end)`` — scan iterations
    ``[begin, end)`` run over the first ``rows`` sorted rows.  The first
    epoch begins at the earliest start, so iterations where NO row is
    live (a running step ceiling above the wave's deepest row) are
    skipped outright.

    ``compaction`` selects the boundary set:

    * ``"full"`` — an epoch boundary at every distinct start: no row ever
      rides frozen, total scheduled row-iterations equal the true sum of
      per-row steps;
    * an ``int`` K — at most K epochs: full boundaries merged greedily,
      always dropping the boundary whose removal adds the fewest frozen
      row-iterations;
    * ``"auto"`` — a boundary is kept when the frozen row-iterations it
      saves outweigh its compile cost: rows arriving at start u save
      ``count(u) * (u - epoch_begin)`` iterations, and the cut costs
      ``compile_cost`` row-iteration-equivalents unless the segment
      geometry ``(carried, rows, length)`` — granule-rounded, exactly the
      key a jitted segment executable specializes on — is already in
      ``geoms``, the caller's shape-bucket cache of compiled segment
      geometries, which makes a split free once its executable exists
      (e.g. the same wave shape recurring across drains).

    ``granule`` rounds each epoch's row count up (to keep segment batches
    divisible by a mesh's data axes); the extra rows are future arrivals
    admitted early — frozen by the active mask until their start, so the
    rounding never changes a row's value, only the schedule.
    """
    steps = np.asarray(steps, np.int32).reshape(-1)
    B, S = len(steps), int(max_steps)
    if B == 0:
        raise ValueError("plan_epochs: empty wave")
    if steps.min() < 1:
        raise ValueError(f"plan_epochs: step counts must be >= 1, got "
                         f"{int(steps.min())}")
    if steps.max() > S:
        raise ValueError(f"plan_epochs: max_steps={S} < largest row step "
                         f"count {int(steps.max())}")
    starts = S - steps
    order = np.argsort(starts, kind="stable")
    ss = starts[order]
    events = [(int(u), int(c)) for u, c in
              zip(*np.unique(ss, return_counts=True))]   # ascending starts

    def _rounded(rows):
        return min(-(-rows // granule) * granule, B) if granule > 1 else rows

    if compaction == "full":
        bounds = [u for u, _ in events]
    elif isinstance(compaction, int) and not isinstance(compaction, bool):
        if compaction < 1:
            raise ValueError(f"plan_epochs: K={compaction} < 1")
        bounds = [u for u, _ in events]
        while len(bounds) > compaction:
            # drop the boundary whose removal freezes the fewest row-iters:
            # arrivals in its epoch ride from the previous boundary instead
            costs = []
            for i in range(1, len(bounds)):
                hi = bounds[i + 1] if i + 1 < len(bounds) else S
                arriving = sum(c for u, c in events if bounds[i] <= u < hi)
                costs.append((arriving * (bounds[i] - bounds[i - 1]), i))
            bounds.pop(min(costs)[1])
    elif compaction == "auto":
        geoms = geoms or set()
        bounds = [events[0][0]]
        live = events[0][1]
        carried = 0        # rows the would-be segment inherits (= the
                           # previous closed segment's rounded row count)
        for u, c in events[1:]:
            length = u - bounds[-1]
            cut_cost = (0 if (carried, _rounded(live), length) in geoms
                        else int(compile_cost))
            if c * length >= cut_cost:
                bounds.append(u)
                carried = _rounded(live)
            live += c
    else:
        raise ValueError(f"plan_epochs: unknown compaction={compaction!r} "
                         f"(expected 'full', 'auto', or an int K)")

    epochs = []
    for i, b0 in enumerate(bounds):
        b1 = bounds[i + 1] if i + 1 < len(bounds) else S
        rows = _rounded(int(np.searchsorted(ss, b1, side="left")))  # start < b1
        epochs.append((rows, b0, b1))
    return order, tuple(epochs)


def reverse_sample_segment(params, dc: DiffusionConfig, x, y, row_keys,
                           guidance, ts, ab_t, ab_prev, jloc, *,
                           image_size: int, channels: int = 3,
                           eta: float = 1.0, use_pallas: bool = False):
    """One compaction epoch: advance the carried rows and admit the new.

    ``x`` is the previous segment's output (the first ``x.shape[0]`` rows
    of this segment); rows ``x.shape[0]:`` activate here — their x_T is
    drawn from ``fold_in(row_keys[b], 0)``, the SAME draw the one-shot
    ragged scan makes up front, so admitting a row late never changes its
    trajectory.  Tables are the ``[:rows, begin:end]`` slices of the
    wave's ``ragged_tables``.  Returns x UNCLIPPED (the trajectory
    continues into the next segment; ``reverse_sample_compacted`` clips
    once at the end)."""
    n_prev = x.shape[0]
    H = image_size
    kx = jax.vmap(lambda k: jax.random.fold_in(k, 0))(row_keys[n_prev:])
    x_new = jax.vmap(lambda k: jax.random.normal(k, (H, H, channels)))(kx)
    x = jnp.concatenate([x, x_new], axis=0)
    B = x.shape[0]
    null = jnp.broadcast_to(params["null_y"], (B, dc.cond_dim))
    y2 = jnp.concatenate([y, null], axis=0)
    guidance = jnp.asarray(guidance, jnp.float32)
    return _ragged_scan(params, dc, x, y2, row_keys, guidance,
                        ts, ab_t, ab_prev, jloc, eta=eta,
                        use_pallas=use_pallas)


def reverse_sample_compacted(params, dc: DiffusionConfig, y, row_keys,
                             guidance, ts, ab_t, ab_prev, jloc, *,
                             epochs, order=None, image_size: int,
                             channels: int = 3, eta: float = 1.0,
                             use_pallas: bool = False, segment_fn=None,
                             mode=None, clf_ids=None, labels=None,
                             clf_fns=()):
    """Compute-skipping ragged reverse process: nested activation waves.

    Runs one scan segment per epoch from ``plan_epochs`` — each over only
    the rows live by that epoch's end — and stitches the segments back
    into REQUEST order (``order`` from ``plan_epochs``; pass ``None`` if
    inputs are already activation-sorted).  Bit-exact vs
    ``reverse_sample_ragged`` on the same tables: row noise is keyed by
    request identity (``row_keys``), frozen iterations are the identity
    on x, and every segment runs the same scan body — so skipping a
    frozen row's iterations cannot change any row's value.

    ``segment_fn`` defaults to ``reverse_sample_segment``; callers that
    want one compiled executable per segment geometry pass a jitted
    wrapper (``sampler._compacted_segment``).

    Passing ``mode`` (with ``clf_ids``/``labels``/``clf_fns``) selects
    the MIXED-guidance segment contract: the per-row mode/classifier
    operands are permuted and sliced alongside every other row vector
    and forwarded to ``segment_fn`` as keyword arguments (default
    ``reverse_sample_mixed_segment``)."""
    mixed = mode is not None
    if segment_fn is None:
        segment_fn = (reverse_sample_mixed_segment if mixed
                      else reverse_sample_segment)
    if mixed:
        mode = np.asarray(mode, np.float32).reshape(-1)
        clf_ids = np.asarray(
            clf_ids if clf_ids is not None else np.zeros_like(mode),
            np.int32).reshape(-1)
        labels = np.asarray(
            labels if labels is not None else np.zeros_like(mode),
            np.int32).reshape(-1)
    if order is not None:
        idx = np.asarray(order)
        y, row_keys = y[idx], row_keys[idx]
        guidance = jnp.asarray(guidance, jnp.float32)[idx]
        ts, ab_t = ts[idx], ab_t[idx]
        ab_prev, jloc = ab_prev[idx], jloc[idx]
        if mixed:
            mode, clf_ids, labels = mode[idx], clf_ids[idx], labels[idx]
    H = image_size
    n_total = y.shape[0]
    if not epochs:
        raise ValueError("reverse_sample_compacted: empty epoch plan")
    if epochs[-1][0] != n_total:
        raise ValueError(
            f"epochs cover {epochs[-1][0]} rows; wave has {n_total}")
    # a caller-supplied plan must have the shape plan_epochs guarantees —
    # contiguous non-empty segments with nondecreasing row counts that
    # run the tables to their final iteration; a gap or an early stop
    # would silently return half-denoised rows
    S = ts.shape[1]
    if epochs[0][1] < 0:
        raise ValueError(f"reverse_sample_compacted: epoch begins at "
                         f"iteration {epochs[0][1]} < 0")
    prev_end, prev_rows = epochs[0][1], 1
    for rows, begin, end in epochs:
        if begin != prev_end or end <= begin or not (prev_rows <= rows
                                                     <= n_total):
            raise ValueError(
                f"reverse_sample_compacted: malformed epoch "
                f"({rows}, {begin}, {end}) — epochs must be contiguous, "
                f"non-empty, with nondecreasing row counts")
        prev_end, prev_rows = end, rows
    if prev_end != S:
        raise ValueError(
            f"reverse_sample_compacted: epochs stop at iteration "
            f"{prev_end}; tables span {S}")
    # ...and every iteration a row is ACTIVE (jloc >= 0, monotone per
    # row) must be computed by an epoch that includes the row: rows a
    # plan skips — before the first epoch, or above an epoch's row count
    # — must be frozen there, or their scan starts mid-trajectory from
    # fresh x_T
    jl = np.asarray(jloc)
    b0 = epochs[0][1]
    if b0 > 0 and not (jl[:, b0 - 1] < 0).all():
        raise ValueError(
            f"reverse_sample_compacted: rows are active before the first "
            f"epoch (begin {b0}) — their leading iterations would be "
            f"skipped")
    for rows, begin, end in epochs:
        if rows < n_total and not (jl[rows:, end - 1] < 0).all():
            raise ValueError(
                f"reverse_sample_compacted: epoch ({rows}, {begin}, {end}) "
                f"excludes rows that are active within it")
    x = jnp.zeros((0, H, H, channels))
    for rows, begin, end in epochs:
        kw = dict(image_size=H, channels=channels, eta=eta,
                  use_pallas=use_pallas)
        if mixed:
            kw.update(mode=mode[:rows], clf_ids=clf_ids[:rows],
                      labels=labels[:rows], clf_fns=clf_fns)
        x = segment_fn(params, dc, x, y[:rows], row_keys[:rows],
                       guidance[:rows], ts[:rows, begin:end],
                       ab_t[:rows, begin:end], ab_prev[:rows, begin:end],
                       jloc[:rows, begin:end], **kw)
    x = jnp.clip(x, -1.0, 1.0)
    if order is not None:
        inv = np.empty_like(idx)
        inv[idx] = np.arange(len(idx))
        x = x[inv]
    return x
