"""Batched D_syn synthesis engine: wave-scheduled diffusion sampling.

The OSCAR server's hot path is generating D_syn from uploaded category
encodings (paper §IV, Eq. 8/9).  ``SynthesisEngine`` is the serving
substrate for that path, mirroring ``ServeEngine``'s wave scheduler for
the LM runtime:

* requests — (encoding, category, count) triples, or classifier-guided /
  unconditional variants — are expanded into per-sample conditioning rows
  held in LIVE PER-GROUP QUEUES; the wave packer peels rows off a group's
  queue one wave at a time, so requests admitted mid-drain (streaming
  mode) fill partially-empty waves instead of forcing padding;
* in snapshot mode (``run`` without ``poll``) a group of N rows is packed
  into NEAR-UNIFORM WAVES: one wave size
  ``w = ceil(N / ceil(N/wave_size) / g) * g`` so every wave of the group
  shares ONE compiled reverse trajectory (the seed-era per-method chunk
  loops compiled a fresh executable for every ragged tail shape) and
  padding is bounded by one granule per wave;  in streaming mode waves
  are ``wave_size`` rows and only the final tail is rounded (down) to a
  granule multiple — less padding at the cost of one extra tail shape;
* waves are DOUBLE-BUFFERED: wave k+1's host-side row packing and
  ``device_put`` overlap wave k's device step loop; the host fences on
  ``jax.block_until_ready`` only when retiring wave k, so packing cost
  disappears from the critical path (disable with ``async_waves=False``);
* wave batches are optionally sharded over the data axes of a mesh
  (``sharding/rules.py`` + ``launch/mesh.py``) — the granule is rounded up
  so every wave divides the data-parallel device count;
* per-encoding outputs are cached keyed by (encoding-hash, guidance,
  steps): resubmitting an encoding serves from cache and a larger count
  only generates the top-up rows (how benchmark sweeps over
  samples-per-category reuse earlier synthesis).  With a persistent
  ``serve/store.py::SynthesisStore`` attached the cache spills to disk,
  so a cold process serves repeated workloads with zero sampler calls.

In GROUPED mode waves are grouped by (mode, guidance,
steps[, classifier identity]) — classifier-guided requests batch per
uploaded classifier, classifier-free requests batch across every client
and category in the queue.

RAGGED WAVES (``ragged=True``): guidance scale and step count become
PER-ROW, and EVERY guidance mode merges into ONE live queue — cfg,
classifier-guided, and unconditional requests share waves instead of
each padding and compiling their own.  One compiled
(wave_rows, max_steps) trajectory serves a mixed (mode, guidance,
steps, classifier) workload: the guidance sweep's groups, FedDISC's
resampled-statistics requests, OSCAR's uploads, FedCADO-style uploaded
classifiers, and unguided draws all ride the same waves.  Unconditional
rows are the s=0 degenerate point of the cfg combine with an explicit
null conditioning row (bit-identical to ``dit_apply``'s y=None
broadcast); classifier-guided rows carry a slot into the engine's
classifier-ensemble registry, and the wave's per-row ε̂-correction
(Eq. 4) selects each row's classifier by that slot — per-sample
classifier evaluations, so a row's value is independent of what else is
batched with it.  A wave with no classifier rows dispatches the pure
cfg executable (grouped-uncond waves count stays zero either way).
Shorter-step rows are right-aligned inside the shared scan and frozen
by an active mask until their trajectory starts; each row's noise
stream is keyed by ``fold_in(fold_in(drain_key, rid), row_index)`` —
the row's identity, not its wave position or mode neighborhood — so
results are bit-independent of how the packer interleaved modes,
streamed arrivals, or padded the wave, and bit-identical to the same
engine serving each mode in isolation.  Cache/store keys stay
(encoding-hash, guidance, steps) (uncond: a synthetic per-category
key), so ragged and grouped engines share a warm store transparently.

COMPACTION (``compaction="auto" | "full" | K``, implies ``ragged``): the
one-shot ragged scan still runs every row through the wave's full step
ceiling — frozen right-aligned rows ride the denoiser before they
activate (the ``row_iters_scheduled`` vs ``row_iters_active`` gap).  A
compacted wave instead runs one scan SEGMENT per activation epoch
(``diffusion/guidance.py::plan_epochs``): rows sorted by start iteration,
each segment's batch holding only the rows live by its end — nested
waves that grow as rows activate — and segment outputs stitched back
into request order.  Row noise stays keyed by request identity, so
compacted output is BIT-IDENTICAL to ragged (and to any other packing);
only the schedule changes.  ``"full"`` puts a boundary at every distinct
start (scheduled == active == the true sum of per-row steps); an int
caps the epoch count; ``"auto"`` keeps a boundary when the frozen
row-iterations it saves outweigh ``compaction_compile_cost``, consulting
the engine's shape-bucket cache of already-compiled segment geometries
(``(carried, rows, iterations)``) so a split that reuses an executable
from an earlier wave or drain is free.

TOPOLOGY (``topology=HostTopology(...)`` or ``hosts=H``): the drain is
placed over H hosts instead of one monolithic packer
(``serve/topology.py``).  Every classifier-free request is routed to a
host's INGRESS QUEUE by its identity (``rid % H``); each host packs its
own contiguous WINDOW of every wave locally (padding is per-window), and
the wave's per-row (ᾱ_t, ᾱ_prev, s, active) scalars live in ONE
wave-resident table that each window's scan reads through the
segment-offset ``cfg_fuse`` path (``cfg_update_rowwise(row_offset=
window.offset)``) — no per-host sliced copies.  Under a topology every
cfg wave (grouped OR ragged) samples row-keyed, so D_syn is
BIT-IDENTICAL regardless of host count, placement, or arrival order —
and identical to a plain ``ragged=True`` engine serving the same
requests.  Compaction composes per window: each host activation-sorts
and epoch-plans its own window, so its segments stay contiguous
row-windows of the wave table.  All hosts run in one process.  A
topology built from a serving mesh (``HostTopology.from_mesh``) places
each host's window on its own submesh (``host_submesh``), with the
denoiser's parameters replicated there once; a simulated topology runs
every window on the default device.
Under ragged scheduling EVERY mode places (classifier-guided and uncond
rows ride the merged waves, so they shard by rows like any cfg row —
the per-row correction batches the classifier over the window); in
grouped mode clf/uncond groups keep the single-host path.  Per-host
accounting lands in ``stats["per_host"]``.

CONCURRENT PLACED DRAIN (``workers=True``, the default): every live
host gets its own EXECUTOR THREAD (``_HostPool``), and a placed wave
runs in two parallel phases — each host packs its window on its own
worker (``np.concatenate``, meta building, ``plan_epochs``, all
overlapping other hosts' work), then, after the wave-resident table is
assembled, each host dispatches its window's jitted segment chain on
its worker WITHOUT fencing.  Retirement fences every window
concurrently on its host's worker, so a ``device.scan`` span times only
its own host's wait (the sequential drain fenced in window order — host
1's span silently measured host 0's).  Concurrency is VALUE-INVISIBLE:
row noise is keyed by request identity and scatter order is fixed by
the placement, so D_syn is bit-identical under any thread interleaving
— and to the ``workers=False`` sequential oracle.  A ``HostLostError``
raised inside a worker (the ``window`` fault site fires there) is
marshalled back to the drain loop after every in-flight dispatch is
collected, and takes the same ``_handle_host_loss`` failover path;
hosts lost CONCURRENTLY in one wave ride along on the first error.

PER-HOST STREAMING ADMISSION (``run(host_polls={h: hook})``): each
host's frontend can poll its own arrival trace — every hook runs at
every wave boundary (it may submit; identity routing places the
request), and any hook returning truthy keeps the drain alive when the
queues run dry, exactly like the global ``poll``.

Requests stay on the queue until their results are produced OR they
resolve to a typed failure: an exception mid-drain (a failing sampler,
an interrupted process) leaves every unserved request queued for the
next ``run``, and rows already produced by the failed drain are CARRIED
to that next ``run`` — exception → re-drain serves every admitted
request with zero loss, whether or not the caller streamed results
through ``on_result``.

FAULT TOLERANCE (``faults=FaultInjector(...)``, ``retry=RetryPolicy()``,
``serve/faults.py``): the drain checks injectable fault SITES —
``window`` (host-window dispatch), ``scan`` (the device fence) — and
recovers instead of aborting.  A transient scan fault retries under the
engine's ``RetryPolicy``; a lost host (``HostLostError`` from a window
dispatch) triggers FAILOVER: ``topology.mark_failed`` removes it, the
aborted wave's rows are un-taken back onto their queues, the dead host's
admitted requests migrate to survivors' ingress queues, and the drain
re-quotas through the same ``wave_quotas``/``WavePlacement.plan`` path.
D_syn stays bit-identical to the fault-free run under ANY fault
schedule because row noise is keyed by request identity — failover is a
placement change, not a resample.  With ``run(on_error=...)`` a
PERMANENT group failure (e.g. a poisoned classifier closure) is
isolated: every unserved request of that group resolves to a
``RequestFailedError`` through the hook and the drain continues serving
other groups.
"""
from __future__ import annotations

import hashlib
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.oscar import DiffusionConfig
from repro.diffusion.guidance import plan_epochs, ragged_tables
from repro.diffusion.sampler import (_window_segment, _window_segment_mixed,
                                     sample_cfg, sample_cfg_compacted,
                                     sample_cfg_ragged,
                                     sample_classifier_guided, sample_mixed,
                                     sample_mixed_compacted, sample_uncond)
from repro.diffusion.schedule import NoiseSchedule
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.serve.faults import (AllHostsLostError, FaultInjector,
                                HostLostError, RequestFailedError,
                                RetryPolicy)
from repro.serve.topology import HostTopology, WavePlacement


def _encoding_hash(encoding: np.ndarray) -> str:
    return hashlib.sha1(np.ascontiguousarray(encoding, np.float32)
                        .tobytes()).hexdigest()


@jax.jit
def _wave_row_keys(key, rids, ridx):
    """``fold_in(fold_in(key, rid), row)`` for each row of a wave: one
    program under a stable name in the device trace."""
    return jax.vmap(
        lambda r, i: jax.random.fold_in(jax.random.fold_in(key, r), i)
    )(rids, ridx)


@dataclass
class SynthesisRequest:
    rid: int
    mode: str                      # "cfg" | "clf" | "uncond"
    count: int
    category: int
    guidance: float
    num_steps: int
    cond: Optional[np.ndarray] = None      # (cond_dim,) for mode="cfg"
    logprob_fn: Optional[Callable] = None  # for mode="clf"
    group: Any = None                      # wave-affinity key for mode="clf"
    cache_key: Optional[tuple] = None


@dataclass
class _Pending:
    """A request admitted into a drain: ``fresh`` rows still to generate
    (count minus cache/planned coverage), packed into waves row by row."""
    req: SynthesisRequest
    fresh: int
    taken: int = 0                               # rows handed to waves
    chunks: list = field(default_factory=list)   # retired output slices

    def rows_left(self) -> int:
        return self.fresh - self.taken

    def row_block(self, k: int, start: int, null=None) -> np.ndarray:
        """Rows ``start:start+k`` of this request's fresh conditioning.
        A 1-D cfg encoding repeats one row; a 2-D encoding (one DISTINCT
        conditioning per sample, e.g. FedDISC's resampled statistics)
        slices — offset past the cached prefix, which covered the leading
        rows.  ``null`` (the DM's null conditioning row) is passed on the
        MERGED ragged path, where clf/uncond rows ride cfg waves as
        explicit null-cond rows (``dit_apply(y=None)`` broadcasts the
        same row, so the values are bit-identical); without it the legacy
        grouped packers get their int label/placeholder blocks."""
        r = self.req
        if r.mode == "cfg":
            if r.cond.ndim == 2:
                off = r.count - self.fresh + start
                return r.cond[off:off + k]
            return np.repeat(r.cond[None], k, axis=0)
        if null is not None:
            return np.repeat(null[None], k, axis=0)
        if r.mode == "clf":
            return np.full((k,), r.category, np.int32)
        return np.zeros((k,), np.int32)          # uncond placeholder ids

    def done_rows(self) -> int:
        return sum(len(c) for c in self.chunks)


class _GroupQueue:
    """Live FIFO of pending requests sharing one wave group — the packer
    consumes from here, so admissions mid-drain extend open waves."""

    def __init__(self, head: SynthesisRequest):
        self.head = head                          # defines mode/g/steps/clf
        self.items: deque[_Pending] = deque()
        # every pending ever pushed here: ``take`` pops exhausted items
        # off the live deque, so failure handling needs this registry to
        # enumerate the group's full admitted population
        self.admitted: list[_Pending] = []

    def push(self, p: _Pending):
        self.items.append(p)
        if not any(q is p for q in self.admitted):
            self.admitted.append(p)

    def rows_available(self) -> int:
        return sum(p.rows_left() for p in self.items)

    def take(self, k: int) -> list[tuple[_Pending, int, int]]:
        """Peel up to ``k`` rows off the queue front, FIFO.  Returns
        (pending, rows_taken, start_row) triples."""
        parts: list[tuple[_Pending, int, int]] = []
        while k > 0 and self.items:
            p = self.items[0]
            t = min(p.rows_left(), k)
            if t:
                parts.append((p, t, p.taken))
                p.taken += t
                k -= t
            if p.rows_left() == 0:
                self.items.popleft()
        return parts


class _ShardedGroup:
    """Per-host ingress for one wave group under a topology: one live
    ``_GroupQueue`` per host, so each host packs its window of a placed
    wave from its own queue (and streams its own late arrivals)."""

    def __init__(self, head: SynthesisRequest, num_hosts: int):
        self.head = head
        self.queues = [_GroupQueue(head) for _ in range(num_hosts)]

    def push(self, p: _Pending, host: int):
        self.queues[host].push(p)

    def rows_available(self) -> int:
        return sum(q.rows_available() for q in self.queues)


class _HostPool:
    """One single-thread executor per live host — the concurrency
    substrate of the placed drain.  A host's pack / dispatch / fence
    tasks run IN ORDER on its own worker (per-host FIFO preserves the
    dispatch-before-fence pipeline), while different hosts' tasks
    overlap freely.  ``discard`` retires exactly one host's worker
    (failover: survivors' threads are untouched); ``close`` joins
    everything at drain end."""

    def __init__(self, hosts):
        self._ex = {h: ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"synth-host{h}")
            for h in sorted(hosts)}

    @property
    def hosts(self) -> frozenset:
        return frozenset(self._ex)

    def submit(self, host: int, fn, *args):
        return self._ex[host].submit(fn, *args)

    def discard(self, host: int):
        """Retire one host's worker (called with no task in flight —
        the drain collects every future before handling a loss)."""
        ex = self._ex.pop(host, None)
        if ex is not None:
            ex.shutdown(wait=False)

    def close(self):
        for ex in self._ex.values():
            ex.shutdown(wait=True)
        self._ex = {}


class SynthesisEngine:
    """Wave-based batched diffusion synthesis over a frozen DM."""

    def __init__(self, dm_params, dc: DiffusionConfig, sched: NoiseSchedule,
                 *, image_size: int, channels: int = 3, wave_size: int = 128,
                 eta: float = 1.0, use_pallas: bool = False, mesh=None,
                 cache: bool = True, granule: int = 8, store=None,
                 async_waves: bool = True, ragged: bool = False,
                 compaction: int | str | None = None,
                 compaction_compile_cost: int = 256,
                 topology: HostTopology | None = None,
                 hosts: int | None = None,
                 workers: bool = True,
                 tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None,
                 faults: FaultInjector | None = None,
                 retry: RetryPolicy | None = None):
        self.dm_params, self.dc, self.sched = dm_params, dc, sched
        self.image_size, self.channels = image_size, channels
        self.eta, self.use_pallas = eta, use_pallas
        self.mesh = mesh
        self._data_sharding = None
        if mesh is not None:
            from repro.launch.mesh import mesh_axes
            ax = mesh_axes(mesh)
            data_names = ax.data
            dsize = int(np.prod([mesh.shape[n] for n in data_names]))
            granule = -(-granule // dsize) * dsize      # waves divide data axes
            self._data_sharding = NamedSharding(mesh, P(ax.all_data, None))
        self.granule = granule
        self.wave_size = max(-(-wave_size // granule) * granule, granule)
        self.cache_enabled = cache
        self.store = store                       # SynthesisStore | None
        self.async_waves = async_waves
        self.ragged = ragged
        self.compaction = None
        self.compaction_compile_cost = compaction_compile_cost
        if compaction is not None:
            self.set_compaction(compaction)
        self.topology = None
        # per-(window offset, wave width) shape buckets of compiled window-
        # segment geometries: a window executable additionally specializes
        # on its offset and the wave's table width, so "auto" free-split
        # hits must be keyed per window, not pooled with _segment_geoms
        self._window_geoms: dict[tuple, set] = {}
        self._host_shardings: dict[int, Optional[dict]] = {}
        self._host_params: dict[int, Any] = {}
        self._cache: dict[tuple, np.ndarray] = {}
        self._queue: list[SynthesisRequest] = []
        self._next_rid = 0
        self.traj_shapes: set = set()    # distinct compiled wave geometries
        # shape-bucket cache of compiled compaction-segment geometries
        # ((carried, rows, iterations) — the jitted executable's key);
        # plan_epochs treats a split that lands in a bucket as
        # compile-free, so recurring wave shapes compact deeper
        self._segment_geoms: set[tuple] = set()
        # mixed-guidance waves compile their OWN segment executables (the
        # classifier-correction step changes the jaxpr), so their "auto"
        # free-split hits live in a separate bucket from the pure-cfg one
        self._segment_geoms_mixed: set[tuple] = set()
        # classifier-ensemble registry for MERGED ragged waves: uploaded
        # classifier closures, in admission order; a wave row selects its
        # classifier by slot index (meta), and the registry tuple is a
        # static argument of the mixed sampler.  Slots only grow — an
        # ensemble extension retraces, a repeat classifier reuses its slot
        self._clf_fns: list = []
        # the DM's null conditioning row: merged waves pack clf/uncond
        # rows as explicit null-cond rows (bit-identical to dit_apply's
        # y=None broadcast of the same parameter)
        self._null_row = np.asarray(dm_params["null_y"], np.float32)
        # observability: a disabled tracer is the default (near-zero-cost
        # no-op spans/stamps); every counter lives in the registry and
        # the legacy ``stats`` dict is a read-only VIEW over it
        self.tracer = tracer if tracer is not None else Tracer(enabled=False)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # fault tolerance: an injector (tests/chaos drills) and the retry
        # policy transient faults run under; both injectable, no wall-clock
        self.faults = faults
        self.retry = retry if retry is not None else RetryPolicy()
        # rows produced by a drain that raised before returning — the next
        # ``run`` hands them to its caller (zero-loss retry contract)
        self._carried: dict[int, np.ndarray] = {}
        # concurrent placed drain: per-host workers (``_HostPool``), built
        # lazily per drain for the live host set; ``workers=False`` keeps
        # the sequential window loop (the fuzz suites' oracle)
        self.workers = workers
        self._pool: Optional[_HostPool] = None
        # test seam: called as (site, host, wave) from inside worker
        # tasks, so tests can force thread interleavings with a barrier
        self._sync_hook = None
        if topology is not None or hosts is not None:
            self.set_topology(topology if topology is not None else hosts)

    #: legacy counter keys, in the order the pre-registry stats dict
    #: carried them — the view preserves both names and order bit-for-bit
    #: ``generated`` counts REAL rows only (images a caller asked for);
    #: ``scheduled_rows`` counts every device row including alignment
    #: padding — the invariant ``scheduled_rows == generated + padded``
    #: holds on every path (grouped/ragged/compacted/placed)
    _STAT_KEYS = ("requests", "waves", "generated", "scheduled_rows",
                  "padded", "cache_hits",
                  "store_hits", "streamed", "merged_waves",
                  "compiled_shapes", "segments",
                  "row_iters_scheduled", "row_iters_active")
    _HOST_STAT_KEYS = ("rows", "padded", "waves", "row_iters_scheduled",
                       "row_iters_active", "queue_depth_at_start")

    @property
    def stats(self) -> dict:
        """Backward-compatible dict view over the metrics registry: all
        pre-registry keys (including the per-host breakdown under a
        topology) with identical values.  A fresh dict per read — bump
        counters through ``self.metrics``, not this view."""
        m = self.metrics
        s = {k: m.get(k) for k in self._STAT_KEYS}
        if self.topology is not None:
            s["hosts"] = self.topology.num_hosts
            s["per_host"] = [
                {k: m.get(f"host.{k}", host=h)
                 for k in self._HOST_STAT_KEYS}
                for h in range(self.topology.num_hosts)]
        return s

    def set_topology(self, topology):
        """Normalize + apply the placement knob.  ``None`` leaves the
        topology alone; an int H builds one — from the engine's mesh when
        it has one (H host partitions of the data axes), otherwise H
        simulated hosts whose windows round to the engine granule.  Sets
        up the per-host stats breakdown (``stats["per_host"]``); the
        cross-host sums of rows/padded/row_iters equal the global
        counters for every placed (classifier-free) wave.  Re-applying
        an EQUAL topology is a no-op (a shared engine's ``opt_in`` runs
        once per entry point and must not wipe accumulated per-host
        counters); switching to a different topology resets the
        breakdown — counters from another layout cannot be merged."""
        if topology is None:
            return
        if isinstance(topology, bool) or not isinstance(
                topology, (int, HostTopology)):
            raise ValueError(
                f"topology={topology!r}: expected a HostTopology or an "
                f"int host count")
        if isinstance(topology, int):
            topology = (HostTopology.from_mesh(self.mesh, topology)
                        if self.mesh is not None else
                        HostTopology.simulated(topology,
                                               granule=self.granule))
        if topology == self.topology or (
                self.topology is not None
                and topology == replace(self.topology, failed=frozenset())):
            return            # re-threading the same placement (a shared
                              # engine's opt_in runs once per entry point)
                              # must not wipe the per-host accounting —
                              # nor resurrect hosts the engine has marked
                              # failed since the fleet was first threaded
        self.topology = topology
        self._host_shardings = {}
        self._host_params = {}
        # counters from another layout cannot be merged: drop the old
        # breakdown, then materialize zeroed counters for every host so
        # the stats view (and the metrics dump) lists each one
        self.metrics.drop("host.")
        self.metrics.set_gauge("hosts", topology.num_hosts)
        for h in range(topology.num_hosts):
            for k in self._HOST_STAT_KEYS:
                self.metrics.counter(f"host.{k}", host=h)

    def set_compaction(self, compaction):
        """Normalize + apply the compaction knob.  ``None`` leaves the
        mode alone; ``"off"`` disables; ``"full"``/``"auto"``/int K
        enable (compaction implies ragged waves — it schedules the ragged
        per-row tables)."""
        if compaction is None:
            return
        if compaction == "off":
            self.compaction = None
            return
        if compaction not in ("full", "auto") and (
                not isinstance(compaction, int) or isinstance(compaction, bool)
                or compaction < 1):
            raise ValueError(
                f"compaction={compaction!r}: expected 'off', 'full', "
                f"'auto', or an int K >= 1")
        self.compaction = compaction
        self.ragged = True

    def opt_in(self, *, ragged: bool | None = None, compaction=None,
               topology=None, hosts: int | None = None,
               tracer: Tracer | None = None,
               faults: FaultInjector | None = None,
               retry: RetryPolicy | None = None):
        """Thread scheduling knobs from a run entry point, OPT-IN ONLY:
        ``ragged=True`` switches this engine to ragged waves,
        ``compaction`` (``"full"``/``"auto"``/int K) enables compacted
        scheduling, ``topology``/``hosts`` places drains over a host
        topology, and ``tracer`` attaches a span/lifecycle tracer — but
        none of them ever forces a shared engine's mode back:
        ``ragged=False``/``None``, ``compaction="off"``/``None``,
        ``topology=None``/``hosts=None``, and ``tracer=None`` leave it
        alone here (disable directly via the attribute or the ``set_*``
        helpers).  This is THE contract every runner and the service
        constructor share; keep them on this helper."""
        if ragged:
            self.ragged = True
        if compaction != "off":
            self.set_compaction(compaction)
        self.set_topology(topology if topology is not None else hosts)
        if tracer is not None:
            self.tracer = tracer
        if faults is not None:
            self.faults = faults
        if retry is not None:
            self.retry = retry
        return self

    # -- submission -------------------------------------------------------
    def submit(self, encoding, category: int, count: int | None = None, *,
               guidance: float | None = None,
               num_steps: int | None = None) -> int:
        """Classifier-free request (paper Eq. 8/9).  A 1-D ``encoding``
        yields ``count`` samples of one conditioning row; a 2-D
        ``(count, cond_dim)`` encoding carries one DISTINCT conditioning
        per sample (e.g. FedDISC's resampled statistics) as a single
        request — and a single cache/store entry."""
        enc = np.ascontiguousarray(encoding, np.float32)
        if enc.ndim == 2:
            if count is not None and count != len(enc):
                raise ValueError(
                    f"2-D encoding carries {len(enc)} rows; count={count}")
            count = len(enc)
        elif count is None:
            raise ValueError("count is required for a 1-D encoding")
        g, steps = self._resolve(guidance, num_steps)
        ck = (_encoding_hash(enc), g, steps) if self.cache_enabled else None
        return self._push(SynthesisRequest(
            rid=-1, mode="cfg", count=int(count), category=int(category),
            guidance=g, num_steps=steps, cond=enc, cache_key=ck))

    def submit_classifier_guided(self, logprob_fn, category: int, count: int,
                                 *, guidance: float | None = None,
                                 num_steps: int | None = None,
                                 group: Any = None) -> int:
        """Classifier-guided request (Eq. 4 / FedCADO).  ``group`` is the
        wave-affinity key — requests sharing it (one uploaded classifier)
        batch into the same waves.  Not cached: a Python closure has no
        stable identity to key on."""
        g, steps = self._resolve(guidance, num_steps)
        # default group: unique per request — id(fn) is unstable under GC
        # and a collision would sample with the wrong classifier
        return self._push(SynthesisRequest(
            rid=-1, mode="clf", count=int(count), category=int(category),
            guidance=g, num_steps=steps, logprob_fn=logprob_fn,
            group=group if group is not None else ("anon", self._next_rid)))

    def submit_unconditional(self, count: int, *, category: int = -1,
                             num_steps: int | None = None) -> int:
        """Unguided p(x) draws through the null embedding.  Cached/stored
        like cfg requests under a synthetic per-category key (an uncond
        draw is fully determined by (category, steps) — there is no
        encoding to hash), so repeated uncond workloads replay from a
        warm store with zero sampler calls."""
        _, steps = self._resolve(0.0, num_steps)
        ck = ((f"uncond:{int(category)}", 0.0, steps)
              if self.cache_enabled else None)
        return self._push(SynthesisRequest(
            rid=-1, mode="uncond", count=int(count), category=int(category),
            guidance=0.0, num_steps=steps, cache_key=ck))

    # -- draining ---------------------------------------------------------
    def run(self, key, *, poll: Callable[[], bool] | None = None,
            host_polls: dict[int, Callable[[], bool]] | None = None,
            stream: bool | None = None,
            on_result: Callable[[int, np.ndarray], None] | None = None,
            on_error: Callable[[int, Exception], None] | None = None,
            ) -> dict[int, np.ndarray]:
        """Drain the queue.  Returns rid -> (count, H, W, C) images.

        Deterministic in ``key`` and the arrival trace: wave ``i`` of the
        drain samples with ``fold_in(key, i)``.  Cached rows are returned
        as generated by the run that produced them.

        ``poll`` (streaming mode) is called before each wave is packed and
        again before the drain concludes; it may submit new requests —
        compatible ones are packed into the currently-open wave.  Return
        truthy to keep the drain alive when the queue runs dry, falsy once
        the arrival trace is exhausted.  ``stream`` defaults to
        ``poll is not None or bool(host_polls)``; streaming packs
        ``wave_size``-row waves with a granule-rounded tail, snapshot mode
        packs near-uniform waves (one compiled shape per group).

        ``host_polls`` (requires a topology) maps host ids to PER-HOST
        poll hooks — each host's frontend polling its own arrival trace.
        Every live host's hook runs at every wave boundary alongside the
        global ``poll`` (a hook may submit; identity routing places the
        request on its home host's ingress queue), and any hook returning
        truthy keeps the drain alive when the queues run dry.  A hook
        whose host has FAILED is dropped, not called — its trace streams
        nowhere; resubmit through a live frontend.

        ``on_result`` (if given) is called with (rid, rows) the moment
        each request's results exist — this drain's caller (e.g. a
        SynthesisService resolving futures) keeps requests served BEFORE
        a mid-drain failure even though ``run`` raises.

        ``on_error`` (if given) turns a PERMANENT failure inside one wave
        group into per-request ``RequestFailedError``s delivered through
        the hook — the drain continues serving every other group instead
        of aborting (``AllHostsLostError`` still propagates: with no
        survivor nothing can make progress).  Without the hook the first
        group failure raises, preserving the legacy contract.

        Requests are removed from the queue only once their results (or a
        typed failure) are produced — an exception mid-drain keeps every
        unserved request queued, and CARRIES rows the failed drain did
        produce forward to the next ``run``, so exception → re-drain
        serves every admitted request with zero loss.
        """
        stream = ((poll is not None or bool(host_polls))
                  if stream is None else stream)
        if host_polls:
            if self.topology is None:
                raise ValueError("host_polls requires a topology "
                                 "(hosts=H / topology=HostTopology(...))")
            bad = [h for h in host_polls
                   if not 0 <= h < self.topology.num_hosts]
            if bad:
                raise ValueError(
                    f"host_polls hosts {bad} out of range for "
                    f"{self.topology.num_hosts} hosts")
        results: dict[int, np.ndarray] = {}
        failed: dict[int, Exception] = {}
        if self.store is not None:
            # store observability + fault policy ride the engine's —
            # shard I/O spans land on the exported store track
            self.store.bind(self.metrics, self.tracer,
                            faults=self.faults, retry=self.retry)
        if self._carried:
            # rows a previous drain produced but never returned (it
            # raised first): they belong to this run's caller now — the
            # finally block below already dropped their requests from
            # the queue when they were produced
            carried, self._carried = self._carried, {}
            results.update(carried)
            if on_result is not None:
                for rid, rows in carried.items():
                    on_result(rid, rows)
        with self.tracer.span("drain", queued=len(self._queue)):
            try:
                self._drain(key, results, failed, poll=poll,
                            host_polls=host_polls, stream=stream,
                            on_result=on_result, on_error=on_error)
            except BaseException:
                # this drain's caller never sees ``results`` — carry the
                # produced rows so the NEXT run returns them
                self._carried.update(results)
                raise
            finally:
                if self._pool is not None:
                    self._pool.close()     # join every host worker
                    self._pool = None
                if self.store is not None:
                    self.store.flush()
                # in-place removal, not a rebuild: a concurrent submit
                # from another thread (SynthesisService) may append
                # mid-removal and a rebuilt list would silently drop
                # that request
                for r in [r for r in self._queue
                          if r.rid in results or r.rid in failed]:
                    self._queue.remove(r)
        return results

    # -- internals --------------------------------------------------------
    def _resolve(self, guidance, num_steps):
        g = self.dc.guidance_scale if guidance is None else float(guidance)
        return g, int(num_steps or self.dc.sample_timesteps)

    def _push(self, req: SynthesisRequest) -> int:
        req.rid = self._next_rid
        self._next_rid += 1
        self._queue.append(req)
        self.metrics.inc("requests")
        self.tracer.stamp(req.rid, "admit")
        return req.rid

    def _group_key(self, r: SynthesisRequest):
        if self.ragged:
            # one merged super-group for EVERY guidance mode: per-row
            # (mode, guidance, steps, classifier) inside shared ragged
            # waves instead of one wave group per (mode, pair, closure).
            # uncond rows ride as s=0 null-cond cfg rows; clf rows carry
            # a slot into the engine's classifier-ensemble registry.
            # (The key literal stays ("cfg",) for continuity with the
            # cfg-only merged scheduler this generalizes.)
            return ("cfg",)
        clf = ("clf", repr(r.group)) if r.mode == "clf" else ("", "")
        return (r.mode, r.guidance, r.num_steps) + clf

    def _clf_slot(self, fn) -> int:
        """Slot of ``fn`` in the classifier-ensemble registry (identity
        match — closures are not hashable by value), appending on first
        sight.  New classifiers are registered at ADMISSION (drain
        thread), so wave packing — which may run on per-host workers —
        only ever performs read-only lookups."""
        for i, f in enumerate(self._clf_fns):
            if f is fn:
                return i
        self._clf_fns.append(fn)
        return len(self._clf_fns) - 1

    def _cached_rows(self, ck) -> Optional[np.ndarray]:
        """Memory cache, spilling in from the persistent store on miss."""
        rows = self._cache.get(ck)
        if rows is None and self.store is not None:
            rows = self.store.get(ck)
            if rows is not None:
                self._cache[ck] = rows
                self.metrics.inc("store_hits", len(rows))
        return rows

    def _plan_waves(self, n: int) -> tuple[int, int]:
        """(num_waves, wave_rows): near-uniform waves, one compiled shape
        per group, padding < one granule per wave."""
        nw = -(-n // self.wave_size)
        per_wave = -(-n // nw)
        rows = -(-per_wave // self.granule) * self.granule
        return nw, rows

    def _shard(self, arr):
        if self._data_sharding is None:
            return arr
        return jax.device_put(arr, self._data_sharding)

    def _note_shape(self, sig: tuple):
        """Track distinct compiled wave geometries (the jit-static part of
        a wave's sampler signature) — the benchmark's compile-count lens."""
        self.traj_shapes.add(sig)
        self.metrics.set_gauge("compiled_shapes", len(self.traj_shapes))

    def _row_keys(self, meta, key):
        """Per-row noise keys: ``fold_in(fold_in(drain_key, rid),
        row_index)`` — a function of the row's identity, NOT its wave
        position or schedule, so ragged and compacted waves (and any
        packing of either) draw identical streams for the same row."""
        return _wave_row_keys(key,
                              np.asarray([m[2] for m in meta], np.uint32),
                              np.asarray([m[3] for m in meta], np.uint32))

    def _sample_wave_compacted(self, cond_rows, meta, key, max_steps: int):
        """One merged classifier-free wave, iteration-compacted: rows
        sorted by activation, one scan segment per epoch over only the
        live rows, outputs stitched back to request order.  Bit-identical
        to ``_sample_wave_ragged`` on the same rows (row noise is keyed
        by request identity); only the schedule — and therefore
        ``row_iters_scheduled`` — changes.  Returns
        ``(x, scheduled_iters)`` — scheduled counts every device row,
        padding included (it is device work); the caller accounts active
        iters over the real rows only."""
        g = np.array([m[0] for m in meta], np.float32)
        steps = np.array([m[1] for m in meta], np.int32)
        row_keys = self._row_keys(meta, key)
        seg_granule = self.granule if self.mesh is not None else 1
        plan = plan_epochs(steps, max_steps, compaction=self.compaction,
                           granule=seg_granule, geoms=self._segment_geoms,
                           compile_cost=self.compaction_compile_cost)
        _, epochs = plan
        prev = 0
        for rows, begin, end in epochs:
            # the full executable key — a jitted segment specializes on
            # (carried, live, iterations), and plan_epochs' "auto" cost
            # model checks exactly this tuple for free splits
            self._note_shape(("cfg-seg", prev, rows, end - begin))
            self._segment_geoms.add((prev, rows, end - begin))
            prev = rows
        self.metrics.inc("segments", len(epochs))
        x = sample_cfg_compacted(self.dm_params, self.dc, self.sched,
                                 self._shard(jnp.asarray(cond_rows)),
                                 row_keys, jnp.asarray(g), steps,
                                 max_steps=max_steps, plan=plan,
                                 image_size=self.image_size,
                                 channels=self.channels, eta=self.eta,
                                 use_pallas=self.use_pallas)
        scheduled = sum(rows * (end - begin) for rows, begin, end in epochs)
        return x, scheduled

    def _sample_wave_ragged(self, cond_rows, meta, key, max_steps: int):
        """One merged classifier-free wave.  ``meta`` carries one
        (guidance, steps, rid, absolute_row_index) per row; row noise keys
        are ``fold_in(fold_in(drain_key, rid), row_index)`` — a function
        of the row's identity, NOT its wave position, so outputs are
        independent of group interleaving, streaming arrival order, and
        alignment padding."""
        g = np.array([m[0] for m in meta], np.float32)
        steps = np.array([m[1] for m in meta], np.int32)
        row_keys = self._row_keys(meta, key)
        self._note_shape(("cfg-ragged", len(cond_rows), max_steps))
        return sample_cfg_ragged(self.dm_params, self.dc, self.sched,
                                 self._shard(jnp.asarray(cond_rows)),
                                 row_keys, jnp.asarray(g), steps,
                                 max_steps=max_steps,
                                 image_size=self.image_size,
                                 channels=self.channels, eta=self.eta,
                                 use_pallas=self.use_pallas)

    def _mixed_columns(self, meta):
        """The per-row mixed-guidance operands carried in meta columns
        4..6: (mode, clf slot, label) vectors plus the static ensemble
        tuple snapshot for this dispatch."""
        mode = np.array([m[4] for m in meta], np.float32)
        cids = np.array([m[5] for m in meta], np.int32)
        labels = np.array([m[6] for m in meta], np.int32)
        return mode, cids, labels, tuple(self._clf_fns)

    def _sample_wave_mixed(self, cond_rows, meta, key, max_steps: int):
        """One merged MIXED-guidance wave: ``_sample_wave_ragged`` plus
        per-row (mode, classifier slot, label) operands — cfg, classifier-
        guided and uncond rows share one launch and one compiled
        (wave_rows, max_steps, ensemble) executable.  Each row's value is
        bit-identical to the same merged engine serving that row's mode
        alone (row noise is identity-keyed and the per-row classifier
        correction is batch-composition-independent)."""
        g = np.array([m[0] for m in meta], np.float32)
        steps = np.array([m[1] for m in meta], np.int32)
        mode, cids, labels, clf_fns = self._mixed_columns(meta)
        row_keys = self._row_keys(meta, key)
        self._note_shape(("mixed-ragged", len(cond_rows), max_steps,
                          len(clf_fns)))
        return sample_mixed(self.dm_params, self.dc, self.sched,
                            self._shard(jnp.asarray(cond_rows)), row_keys,
                            jnp.asarray(g), mode, cids, labels, steps,
                            clf_fns=clf_fns, max_steps=max_steps,
                            image_size=self.image_size,
                            channels=self.channels, eta=self.eta,
                            use_pallas=self.use_pallas)

    def _sample_wave_mixed_compacted(self, cond_rows, meta, key,
                                     max_steps: int):
        """Iteration-compacted MIXED wave: ``_sample_wave_compacted``'s
        activation epochs with the mixed per-row operands riding along.
        Mixed segments compile their own executables (the classifier
        correction changes the jaxpr), so their "auto" free-split hits
        track in ``_segment_geoms_mixed``, not the pure-cfg bucket."""
        g = np.array([m[0] for m in meta], np.float32)
        steps = np.array([m[1] for m in meta], np.int32)
        mode, cids, labels, clf_fns = self._mixed_columns(meta)
        row_keys = self._row_keys(meta, key)
        seg_granule = self.granule if self.mesh is not None else 1
        plan = plan_epochs(steps, max_steps, compaction=self.compaction,
                           granule=seg_granule,
                           geoms=self._segment_geoms_mixed,
                           compile_cost=self.compaction_compile_cost)
        _, epochs = plan
        prev = 0
        for rows, begin, end in epochs:
            self._note_shape(("mixed-seg", prev, rows, end - begin,
                              len(clf_fns)))
            self._segment_geoms_mixed.add((prev, rows, end - begin))
            prev = rows
        self.metrics.inc("segments", len(epochs))
        x = sample_mixed_compacted(self.dm_params, self.dc, self.sched,
                                   self._shard(jnp.asarray(cond_rows)),
                                   row_keys, jnp.asarray(g), mode, cids,
                                   labels, steps, clf_fns=clf_fns,
                                   max_steps=max_steps, plan=plan,
                                   image_size=self.image_size,
                                   channels=self.channels, eta=self.eta,
                                   use_pallas=self.use_pallas)
        scheduled = sum(rows * (end - begin) for rows, begin, end in epochs)
        return x, scheduled

    def _sample_wave(self, grp_head: SynthesisRequest, cond_rows, key):
        H, C = self.image_size, self.channels
        if grp_head.mode == "cfg":
            self._note_shape(("cfg", len(cond_rows), grp_head.num_steps,
                              grp_head.guidance))
            return sample_cfg(self.dm_params, self.dc, self.sched,
                              self._shard(jnp.asarray(cond_rows)), key,
                              image_size=H, channels=C,
                              num_steps=grp_head.num_steps,
                              guidance=grp_head.guidance, eta=self.eta,
                              use_pallas=self.use_pallas)
        if grp_head.mode == "clf":
            self._note_shape(("clf", repr(grp_head.group), len(cond_rows),
                              grp_head.num_steps, grp_head.guidance))
            return sample_classifier_guided(
                self.dm_params, self.dc, self.sched, grp_head.logprob_fn,
                self._shard(jnp.asarray(cond_rows, jnp.int32)), key,
                image_size=H, channels=C, num_steps=grp_head.num_steps,
                guidance=grp_head.guidance, eta=self.eta,
                use_pallas=self.use_pallas)
        self._note_shape(("uncond", len(cond_rows), grp_head.num_steps))
        return sample_uncond(self.dm_params, self.dc, self.sched,
                             len(cond_rows), key, image_size=H, channels=C,
                             num_steps=grp_head.num_steps, eta=self.eta,
                             use_pallas=self.use_pallas)

    # -- drain machinery --------------------------------------------------
    def _drain(self, key, results, failed, *, poll, stream, host_polls=None,
               on_result=None, on_error=None):
        st = _DrainState()
        st.on_result = on_result
        st.on_error = on_error
        st.failed = failed
        st.tracer = self.tracer       # deliver stamps ride the drain state
        with self.tracer.span("drain.admit"):
            self._admit_new(st, results)
        st.started = True             # later admissions count as streamed
        if self.topology is not None:
            for h, q in enumerate(self._host_depths(st)):
                self.metrics.inc("host.queue_depth_at_start", q, host=h)
        polling = poll is not None or bool(host_polls)
        while True:
            live = sorted(g for g, q in st.groups.items()
                          if q.rows_available())
            if not live:
                if polling:
                    # the queues ran dry: admit only while a hook keeps
                    # the drain alive
                    with self.tracer.span("wave.admit", wave=st.wave_i):
                        more = self._poll_all(poll, host_polls)
                        if more:
                            self._admit_new(st, results)
                    if more:
                        continue
                break
            grp = st.groups[live[0]]
            try:
                if isinstance(grp, _ShardedGroup):
                    self._drain_group_placed(grp, st, key, results,
                                             poll=poll,
                                             host_polls=host_polls,
                                             stream=stream)
                else:
                    self._drain_group(grp, st, key, results, poll=poll,
                                      host_polls=host_polls, stream=stream)
            except Exception as exc:
                # failure isolation: with an on_error hook, a permanent
                # failure inside ONE group (a poisoned classifier, an
                # exhausted retry) fails that group's requests with typed
                # errors and the drain keeps serving everyone else.  No
                # hook → legacy contract: raise, keep queues intact.
                if st.on_error is None or isinstance(exc, AllHostsLostError):
                    raise
                self._fail_group(grp, st, results, exc)
        # any still-unresolved waiters are covered by rows generated above
        self._serve_waiters(st, results)

    def _host_depths(self, st: "_DrainState") -> list[int]:
        """Rows waiting on each host's ingress queues right now."""
        depths = [0] * self.topology.num_hosts
        for grp in st.groups.values():
            if isinstance(grp, _ShardedGroup):
                for h, q in enumerate(grp.queues):
                    depths[h] += q.rows_available()
        return depths

    def _poll_all(self, poll, host_polls) -> bool:
        """Admission keep-alive: run the global ``poll`` AND every live
        host's admission hook.  Every hook runs — no short-circuit,
        because a hook's side effect is submitting that host's requests
        — and any truthy return keeps the drain alive.  Hooks for hosts
        that have since died are dropped: their traffic belongs to
        survivors now, which identity routing over the live set already
        handles at admission."""
        more = False
        if poll is not None:
            more = bool(poll()) or more
        if host_polls:
            live = (self.topology.live_hosts
                    if self.topology is not None else ())
            for h, hook in host_polls.items():
                if h in live:
                    more = bool(hook()) or more
        return more

    def _ensure_pool(self) -> Optional[_HostPool]:
        """The per-host worker pool for the CURRENT live set, or None
        when the drain should stay sequential (``workers=False``, no
        topology, or fewer than two live hosts — one host gains nothing
        from a worker).  Rebuilt only when membership changes; a host
        loss discards just the dead host's executor
        (``_handle_host_loss``), so survivors' threads ride out the
        failover untouched."""
        if not self.workers or self.topology is None:
            return None
        live = frozenset(self.topology.live_hosts)
        if len(live) < 2:
            if self._pool is not None:
                self._pool.close()
                self._pool = None
            return None
        if self._pool is None or self._pool.hosts != live:
            if self._pool is not None:
                self._pool.close()
            self._pool = _HostPool(live)
        return self._pool

    @staticmethod
    def _collect(futs):
        """Gather per-window worker futures in WINDOW ORDER with
        deterministic error marshalling: every future is awaited (no
        task is left running into failover handling), then the first
        error BY WINDOW ORDER — not completion order — is raised,
        exactly what the sequential loop would have raised.  When that
        error is a ``HostLostError``, any further same-wave losses ride
        along as ``err.also`` so ``_handle_host_loss`` can fail every
        dead host from one aborted wave."""
        outs, first, losses = [], None, []
        for f in futs:
            try:
                outs.append(f.result())
            except HostLostError as err:
                losses.append(err)
                if first is None:
                    first = err
            except Exception as exc:          # noqa: BLE001 — re-raised
                if first is None:
                    first = exc
        if first is not None:
            if isinstance(first, HostLostError):
                first.also = [e for e in losses if e is not first]
            raise first
        return outs

    def _check_fault(self, site: str, *, host: int = 0, wave: int = -1):
        """Injectable fault site: counts what fires, then lets it raise."""
        if self.faults is None:
            return
        try:
            self.faults.check(site, host=host, wave=wave)
        except Exception:
            self.metrics.inc("fault.injected", site=site)
            raise

    def _fence(self, x, *, host: int, wave: int):
        """Retire-side device fence with the ``scan`` fault site under
        the engine's retry policy — a transient device hiccup burns
        retries instead of aborting the drain."""
        def attempt():
            self._check_fault("scan", host=host, wave=wave)
            jax.block_until_ready(x)
        self.retry.run(attempt, metrics=self.metrics, site="device.scan")

    def _fail_group(self, grp, st: "_DrainState", results, exc):
        """Resolve every unserved request admitted to ``grp`` to a typed
        ``RequestFailedError`` (cause attached) through the drain's
        ``on_error`` hook, release their cache-coverage claims, fail
        waiters riding a now-uncovered key, and clear the group's queues
        so the drain moves on."""
        queues = grp.queues if isinstance(grp, _ShardedGroup) else [grp]
        doomed = []
        for q in queues:
            for p in q.admitted:
                rid = p.req.rid
                if rid in results or rid in st.failed or \
                        any(d.req.rid == rid for d in doomed):
                    continue
                doomed.append(p)
        bad_keys = set()
        for p in doomed:
            r = p.req
            if r.cache_key is not None:
                # rows this pending claimed in ``planned`` will never be
                # generated; a same-key request must not count on them
                left = st.planned.get(r.cache_key, 0) - p.fresh
                st.planned[r.cache_key] = max(left, 0)
                bad_keys.add(r.cache_key)
            self._fail_request(st, r, exc)
        still = []
        for r in st.waiters:
            cached = self._cache.get(r.cache_key)
            covered = cached is not None and len(cached) >= r.count
            if r.cache_key in bad_keys and not covered:
                self._fail_request(st, r, exc)
            else:
                still.append(r)
        st.waiters = still
        for q in queues:
            q.items.clear()

    def _fail_request(self, st: "_DrainState", r: SynthesisRequest, exc):
        err = RequestFailedError(
            f"request {r.rid} ({r.mode}) failed permanently: {exc}",
            rid=r.rid)
        err.__cause__ = exc
        st.failed[r.rid] = err
        self.metrics.inc("requests_failed")
        self.tracer.instant("request.failed", rid=r.rid)
        if st.on_error is not None:
            st.on_error(r.rid, err)

    def _admit_new(self, st: "_DrainState", results):
        """Admission: serve full cache hits, compute top-up ``fresh`` row
        counts against cache + rows already planned this drain, and push
        the remainder onto their live group queues."""
        for r in list(self._queue):
            if r.rid in st.admitted:
                continue
            st.admitted.add(r.rid)
            if st.started:
                self.metrics.inc("streamed")
            if r.count <= 0:               # degenerate: nothing to generate
                st.deliver(results, r.rid, np.zeros(
                    (0, self.image_size, self.image_size, self.channels),
                    np.float32))
                continue
            have = 0
            if r.cache_key is not None:
                cached = self._cached_rows(r.cache_key)
                have = ((0 if cached is None else len(cached))
                        + st.planned.get(r.cache_key, 0))
            fresh = max(r.count - have, 0)
            self.metrics.inc("cache_hits", r.count - fresh)
            if fresh == 0:
                cached = self._cached_rows(r.cache_key)
                if cached is not None and len(cached) >= r.count:
                    st.deliver(results, r.rid, cached[:r.count].copy())
                else:
                    # covered by rows another request planned this drain —
                    # resolved once the generating wave retires
                    st.waiters.append(r)
                continue
            if r.mode == "clf" and self.ragged:
                # merged-path classifiers are vetted AT ADMISSION: an
                # abstract probe catches a poisoned closure before it is
                # baked into a mixed wave (where it would poison every
                # co-batched request), and registers the survivor's
                # ensemble slot while admission is still single-threaded.
                # With an on_error hook the bad request resolves to a
                # typed failure and the drain continues; without one the
                # legacy first-failure-raises contract holds.
                try:
                    H, C = self.image_size, self.channels
                    jax.eval_shape(
                        r.logprob_fn,
                        jax.ShapeDtypeStruct((1, H, H, C), jnp.float32),
                        jax.ShapeDtypeStruct((1,), jnp.int32))
                    self._clf_slot(r.logprob_fn)
                except Exception as exc:
                    if st.on_error is None:
                        raise
                    self._fail_request(st, r, exc)
                    continue
            if r.cache_key is not None:
                st.planned[r.cache_key] = (st.planned.get(r.cache_key, 0)
                                           + fresh)
            gk = self._group_key(r)
            placed = self.topology is not None and (r.mode == "cfg"
                                                    or self.ragged)
            if gk not in st.groups:
                st.groups[gk] = (_ShardedGroup(r, self.topology.num_hosts)
                                 if placed else _GroupQueue(r))
            self.tracer.stamp(r.rid, "enqueue")
            if placed:
                # ingress routing keyed by request IDENTITY, not arrival
                # order: a replayed trace lands every request on the same
                # host (and any routing is value-invisible anyway — row
                # noise is keyed by the row, not its host)
                st.groups[gk].push(_Pending(r, fresh),
                                   self.topology.assign(r.rid))
            else:
                st.groups[gk].push(_Pending(r, fresh))

    def _drain_group(self, q: _GroupQueue, st: "_DrainState", key, results,
                     *, poll, host_polls, stream):
        """Drain one group's live queue wave by wave, double-buffered:
        wave k+1 is packed and dispatched while wave k runs on device.
        Under ragged scheduling the one merged queue carries EVERY
        guidance mode; a wave with classifier-guided rows dispatches
        through the mixed sampler, a wave without any rides the pure
        cfg path (uncond rows are s=0 null-cond cfg rows there — the
        same arithmetic bit-for-bit)."""
        ragged = self.ragged
        if stream:
            wave_rows = self.wave_size
        else:
            _, wave_rows = self._plan_waves(q.rows_available())
        # ragged step ceiling: a running max, so every wave after the
        # deepest row arrives shares one compiled geometry (row results
        # are max_steps-independent — right-aligned rows just freeze
        # longer), and a drain sees at most one recompile per new deepest
        # step count instead of one per (guidance, steps) group
        smax = 0
        inflight = None                  # (device x, parts, n_real, wave)
        while True:
            # admission runs at every wave boundary with or without a
            # poll, so requests submitted by another thread while waves
            # are in flight stream into this drain too; the span covers
            # taking the wave's rows off the queue
            with self.tracer.span("wave.admit", wave=st.wave_i):
                self._poll_all(poll, host_polls)
                self._admit_new(st, results)
                parts = q.take(wave_rows)
                got = sum(t for _, t, _ in parts)
                if 0 < got < wave_rows:
                    # open wave: give late arrivals one chance to fill it
                    self._poll_all(poll, host_polls)
                    self._admit_new(st, results)
                    more = q.take(wave_rows - got)
                    parts += more
                    got += sum(t for _, t, _ in more)
            if got == 0:
                break
            # tail: snapshot keeps the group-uniform shape, streaming
            # rounds to a granule multiple (one extra compiled tail shape)
            target = (-(-got // self.granule) * self.granule if stream
                      else wave_rows)
            with self.tracer.span("wave.pack", wave=st.wave_i, host=0,
                                  rows=target, real=got):
                rows = np.concatenate(
                    [p.row_block(t, s, self._null_row if ragged else None)
                     for p, t, s in parts])
                meta = None
                if ragged:
                    # (guidance, steps, rid, absolute row index, mode,
                    # clf slot, label) per row; the index offsets past
                    # the cached prefix so a top-up row has the same
                    # identity whichever drain generates it.  mode is
                    # 0 for cfg AND uncond (uncond = s=0 null-cond),
                    # 1 for classifier-guided; slot indexes the engine's
                    # classifier-ensemble registry
                    meta = [(p.req.guidance, p.req.num_steps, p.req.rid,
                             p.req.count - p.fresh + s + i,
                             1.0 if p.req.mode == "clf" else 0.0,
                             (self._clf_slot(p.req.logprob_fn)
                              if p.req.mode == "clf" else 0),
                             p.req.category)
                            for p, t, s in parts for i in range(t)]
                if target > got:
                    rows = np.concatenate(
                        [rows, np.repeat(rows[-1:], target - got, axis=0)])
                    if ragged:
                        # padding duplicates the last row's identity: same
                        # key, same cond — a discarded bit-identical copy
                        # that can never perturb the real rows
                        meta += [meta[-1]] * (target - got)
                for p, _, _ in parts:
                    self.tracer.stamp(p.req.rid, "pack")
            st.wave_i += 1
            with self.tracer.span("wave.dispatch", wave=st.wave_i - 1,
                                  host=0, rows=target,
                                  mode=q.head.mode) as sp:
                if ragged:
                    smax = max(smax, *(m[1] for m in meta))
                    # honest device-work accounting, split two ways:
                    # ``row_iters_active`` is the useful work — each REAL
                    # row's own step count (padding duplicates are
                    # discarded, so they are never useful);
                    # ``row_iters_scheduled`` is what the device actually
                    # ran, padding included.  One-shot ragged schedules
                    # every row for the wave's step ceiling (frozen
                    # right-aligned rows ride the denoiser — the price of
                    # one shared geometry); compaction closes the gap by
                    # skipping frozen epochs.
                    active_iters = int(sum(m[1] for m in meta[:got]))
                    mixed = any(m[4] for m in meta)
                    if self.compaction is not None:
                        sampler = (self._sample_wave_mixed_compacted
                                   if mixed else self._sample_wave_compacted)
                        x, sched_iters = sampler(rows, meta, key, smax)
                    else:
                        sampler = (self._sample_wave_mixed if mixed
                                   else self._sample_wave_ragged)
                        x = sampler(rows, meta, key, smax)
                        sched_iters = target * smax
                    self.metrics.inc("merged_waves")
                    self.metrics.inc("row_iters_scheduled", sched_iters)
                    self.metrics.inc("row_iters_active", active_iters)
                    sp.set(iters_scheduled=sched_iters)
                else:
                    x = self._sample_wave(
                        q.head, rows, jax.random.fold_in(key, st.wave_i - 1))
                    self.metrics.inc("row_iters_scheduled",
                                     target * q.head.num_steps)
                    self.metrics.inc("row_iters_active",
                                     got * q.head.num_steps)
                for p, _, _ in parts:
                    self.tracer.stamp(p.req.rid, "dispatch")
                self.metrics.inc("waves")
                self.metrics.inc("generated", got)
                self.metrics.inc("scheduled_rows", target)
                self.metrics.inc("padded", target - got)
            if inflight is not None:
                self._retire(st, results, *inflight)
            if self.async_waves:
                inflight = (x, parts, got, st.wave_i - 1)
            else:
                self._retire(st, results, x, parts, got, st.wave_i - 1)
        if inflight is not None:
            self._retire(st, results, *inflight)

    def _drain_group_placed(self, grp: _ShardedGroup, st: "_DrainState", key,
                            results, *, poll, host_polls, stream):
        """Placement-aware drain of one group (grouped cfg, or the
        merged all-modes ragged queue) over the engine's topology,
        double-buffered like ``_drain_group``: each host packs
        its contiguous window of every wave locally from its own ingress
        queue (per-window padding, per-window compaction plans), and the
        wave's per-row scalars live in one wave-resident table that every
        window reads through the segment-offset ``cfg_fuse`` path.
        Placed drains quota-pack in BOTH snapshot and streaming mode (the
        per-host quota split replaces ``_plan_waves``' near-uniform
        shapes); admission still runs at every wave boundary, so late
        arrivals stream into open windows either way.  Row noise stays
        keyed by request identity, so outputs are bit-identical for ANY
        topology, placement, or arrival order."""
        smax = 0                         # running step ceiling (see above)
        inflight = None                  # (xs, invs, placement, parts_h, w)
        shapes = set()                   # dispatched (host, rows) geometries
        # snapshot drains spread the group's rows over near-uniform waves
        # (the exact ``_plan_waves`` policy the single-host packer uses):
        # no systematic tail wave, so every wave shares the full waves'
        # window geometry and their compiled executables.  Streaming
        # drains can't know the total up front and keep ``wave_size``.
        if stream or grp.rows_available() == 0:
            wave_target = self.wave_size
        else:
            _, wave_target = self._plan_waves(grp.rows_available())
        while True:
            # re-read topology + quotas EVERY wave: a host lost on the
            # previous iteration re-spreads its share over survivors
            # through the same proportional split (failover == re-quota)
            topo = self.topology
            quotas = topo.wave_quotas(wave_target)
            with self.tracer.span("wave.admit", wave=st.wave_i):
                self._poll_all(poll, host_polls)
                self._admit_new(st, results)
                parts_h = [q.take(quotas[h])
                           for h, q in enumerate(grp.queues)]
                got = sum(t for parts in parts_h for _, t, _ in parts)
                if 0 < got < sum(quotas):
                    # open wave: give late arrivals one chance to fill
                    # the hosts' windows before padding them
                    self._poll_all(poll, host_polls)
                    self._admit_new(st, results)
                    for h, q in enumerate(grp.queues):
                        have = sum(t for _, t, _ in parts_h[h])
                        if have < quotas[h]:
                            parts_h[h] += q.take(quotas[h] - have)
                    got = sum(t for parts in parts_h for _, t, _ in parts)
            if got == 0:
                break
            rows_h = [sum(t for _, t, _ in parts) for parts in parts_h]
            placement = WavePlacement.plan(rows_h, topo.granules)
            geom = tuple((w.host, w.rows) for w in placement.windows)
            if geom not in shapes:
                # tail-wave shape promotion: if padding every window up
                # to its quota reproduces a geometry this drain already
                # dispatched, take it — the tail then reuses the full
                # waves' compiled window executables instead of
                # compiling its own (padding dups are discarded at
                # scatter, so D_syn is unchanged)
                quota_pl = WavePlacement.plan(rows_h, topo.granules,
                                              pad_to=quotas)
                if tuple((w.host, w.rows)
                         for w in quota_pl.windows) in shapes:
                    placement = quota_pl
            # the wave index is BURNED only on successful dispatch (an
            # aborted wave's repack keeps the same index, so trace
            # ``wave=`` ids agree with the ``waves`` counter), and the
            # pack stamp is captured here but committed only after the
            # wave dispatches — first-stamp-wins tracer semantics must
            # not freeze an aborted wave's pack time
            wave = st.wave_i
            t_pack = self.tracer.now()
            deep = max(p.req.num_steps
                       for parts in parts_h for p, _, _ in parts)
            smax_w = max(smax, deep)
            try:
                xs, invs, host_stats = self._sample_wave_placed(
                    parts_h, placement, key, smax_w, wave=wave)
            except HostLostError as err:
                # FAILOVER: the in-flight wave was dispatched before the
                # loss — retire it first; then un-take this wave, migrate
                # the dead hosts' requests to survivors, and re-quota.
                # Row noise is identity-keyed, so the repacked rows are
                # bit-identical — a placement change, not a resample.
                if inflight is not None:
                    self._retire_placed(st, results, *inflight)
                    inflight = None
                self._handle_host_loss(grp, st, parts_h, err)
                continue
            st.wave_i += 1
            smax = smax_w
            shapes.add(tuple((w.host, w.rows) for w in placement.windows))
            for parts in parts_h:
                for p, _, _ in parts:
                    self.tracer.stamp(p.req.rid, "pack", t=t_pack)
                    self.tracer.stamp(p.req.rid, "dispatch")
            self.metrics.inc("waves")
            if self.ragged:
                self.metrics.inc("merged_waves")
            self.metrics.inc("generated", placement.real_rows)
            self.metrics.inc("scheduled_rows", placement.total_rows)
            self.metrics.inc("padded", placement.padded)
            for w, hs in zip(placement.windows, host_stats):
                h = w.host
                self.metrics.inc("host.rows", w.real, host=h)
                self.metrics.inc("host.padded", w.rows - w.real, host=h)
                self.metrics.inc("host.waves", host=h)
                self.metrics.inc("host.row_iters_scheduled",
                                 hs["scheduled"], host=h)
                self.metrics.inc("host.row_iters_active", hs["active"],
                                 host=h)
                self.metrics.inc("row_iters_scheduled", hs["scheduled"])
                self.metrics.inc("row_iters_active", hs["active"])
            if inflight is not None:
                self._retire_placed(st, results, *inflight)
            if self.async_waves:
                inflight = (xs, invs, placement, parts_h, wave)
            else:
                self._retire_placed(st, results, xs, invs, placement,
                                    parts_h, wave)
        if inflight is not None:
            self._retire_placed(st, results, *inflight)

    def _handle_host_loss(self, grp: _ShardedGroup, st: "_DrainState",
                          parts_h, err: HostLostError):
        """Elastic membership: mark the lost host failed (survivors
        re-quota on the next wave), put the aborted wave's rows back on
        their queues (front, pack order), and migrate the dead host's
        admitted REQUESTS — not its padded rows — onto survivors' ingress
        queues by identity routing over the live set.  Migration covers
        EVERY sharded group, not just the one mid-wave: grouped-mode
        drains hold one ``_ShardedGroup`` per (guidance, steps), and a
        request parked on the dead host's queue of a not-yet-drained
        group would otherwise be unreachable (its window quota is 0
        forever) while still counting as available — losing the request
        and livelocking the drain loop."""
        # un-take the whole aborted wave FIRST: restore each pending's
        # ``taken`` and put exhausted (popped) pendings back at the queue
        # front in pack order — identical rows will repack under the new
        # quotas.  Doing this before any ``mark_failed`` keeps the queues
        # whole even when the last survivor dies here (the concurrent
        # dispatch can lose SEVERAL hosts in one wave, carried on
        # ``err.also``) and ``AllHostsLostError`` aborts the drain.
        for hq, parts in zip(grp.queues, parts_h):
            for p, t, _ in parts:
                p.taken -= t
            readd = []
            for p, _, _ in parts:
                if not any(q is p for q in readd) and \
                        not any(q is p for q in hq.items):
                    readd.append(p)
            hq.items.extendleft(reversed(readd))
        for loss in (err, *getattr(err, "also", ())):
            dead = loss.host
            # raises AllHostsLostError when no survivor remains
            topo = self.topology.mark_failed(dead)
            self.topology = topo
            self.metrics.inc("fault.host_lost")
            self.metrics.set_gauge("hosts_live", len(topo.live_hosts))
            self.tracer.instant("host.failed", host=dead, wave=loss.wave)
            if self._pool is not None:
                # retire the dead host's worker only — survivors' threads
                # (and the tasks queued on them) are untouched
                self._pool.discard(dead)
            moved = 0
            for g in st.groups.values():
                if not isinstance(g, _ShardedGroup):
                    continue
                dq = g.queues[dead]
                moved += sum(p.rows_left() for p in dq.items)
                for p in list(dq.items):
                    g.push(p, topo.assign(p.req.rid))
                dq.items.clear()
            self.metrics.inc("failover.requeued_rows", moved)

    def _pack_window(self, w, parts, max_steps: int, total_rows: int,
                     wave: int, mixed: bool = False):
        """Pack ONE host's window: concatenate its pending row blocks,
        build per-row meta, pad, and (under compaction) plan the
        window's epoch segments with its activation sort.  Host-LOCAL
        work — it touches only this host's pendings and this window's
        ``_window_geoms`` bucket, so the per-host workers run packs for
        different hosts concurrently.  ``mixed`` is the WAVE-level flag
        (any window of the wave holds a classifier-guided row): mixed
        window segments are distinct executables, so their "auto"
        free-split hits bucket separately.  Returns ``(rows, meta, inv,
        epochs, stats)``."""
        with self.tracer.span("window.pack", wave=wave, **w.span_attrs):
            rows = np.concatenate(
                [p.row_block(t, s, self._null_row if self.ragged else None)
                 for p, t, s in parts])
            # (guidance, steps, rid, absolute row index, mode, clf slot,
            # label) — identical row identity to the single-host packers,
            # so any engine serving these requests draws the same noise
            # streams; the mixed columns are inert for pure-cfg waves
            meta = [(p.req.guidance, p.req.num_steps, p.req.rid,
                     p.req.count - p.fresh + s + i,
                     1.0 if p.req.mode == "clf" else 0.0,
                     (self._clf_slot(p.req.logprob_fn)
                      if p.req.mode == "clf" else 0),
                     p.req.category)
                    for p, t, s in parts for i in range(t)]
            if w.rows > w.real:
                # per-window padding duplicates the window's OWN last
                # row (same identity → a discarded bit-identical copy)
                rows = np.concatenate(
                    [rows,
                     np.repeat(rows[-1:], w.rows - w.real, axis=0)])
                meta += [meta[-1]] * (w.rows - w.real)
            # useful work: each REAL row's own step count, pre-sort
            active = int(sum(m[1] for m in meta[:w.real]))
            steps_w = np.array([m[1] for m in meta], np.int32)
            if self.compaction is not None:
                seg_granule = (self.topology.granules[w.host]
                               if self.mesh is not None else 1)
                geoms = self._window_geoms.setdefault(
                    (w.offset, total_rows, "mixed") if mixed
                    else (w.offset, total_rows), set())
                order, epochs = plan_epochs(
                    steps_w, max_steps, compaction=self.compaction,
                    granule=seg_granule, geoms=geoms,
                    compile_cost=self.compaction_compile_cost)
                rows = rows[order]
                meta = [meta[i] for i in order]
                inv = np.empty_like(order)
                inv[order] = np.arange(len(order))
            else:
                # one segment spanning the whole scan: right-aligned
                # rows ride frozen, exactly like the one-shot ragged
                # wave
                epochs, inv = ((w.rows, 0, max_steps),), None
            return rows, meta, inv, epochs, \
                {"active": active,
                 "scheduled": sum(r * (e - b) for r, b, e in epochs)}

    def _dispatch_window(self, w, epochs, ctx, wave: int):
        """Dispatch ONE host window's jitted segment chain — device_put
        through the host submesh shardings, then enqueue every epoch
        segment — WITHOUT fencing: JAX's async dispatch returns as soon
        as the work is enqueued, so back-to-back (or per-host-worker)
        calls overlap host h+1's dispatch with host h's device scan.
        ``_retire_placed`` fences the returned output later."""
        y, row_keys, g, ts, ab_t, ab_prev, jloc, act, B, mx = ctx
        # the host-window dispatch fault site: a fault here models the
        # host dying with its window undispatched — the drain's failover
        # path requeues the wave and carries on
        self._check_fault("window", host=w.host, wave=wave)
        lo = w.offset
        sh = self._window_shardings(w.host)
        params = self._window_params(w.host)
        x = jnp.zeros((0, self.image_size, self.image_size,
                       self.channels))
        prev = 0
        with self.tracer.span("window.dispatch", wave=wave,
                              segments=len(epochs), **w.span_attrs):
            for rows, begin, end in epochs:
                # full executable key: a window segment specializes on
                # (wave width, carried, live, iterations) — NOT the
                # window offset, which is a traced operand, so equal-
                # quota hosts share one executable per segment geometry.
                # Mixed waves additionally key on the ensemble tuple.
                if mx is not None:
                    self._note_shape(("mixed-win", B, prev, rows,
                                      end - begin, len(mx[3])))
                else:
                    self._note_shape(("cfg-win", B, prev, rows,
                                      end - begin))
                if self.compaction is not None:
                    gk = (lo, B, "mixed") if mx is not None else (lo, B)
                    self._window_geoms[gk].add((prev, rows, end - begin))
                    self.metrics.inc("segments")
                hi = lo + rows
                args = dict(y=y[lo:hi], rk=row_keys[lo:hi], g=g,
                            ts=ts[lo:hi, begin:end],
                            jloc=jloc[lo:hi, begin:end],
                            ab_t=ab_t[:, begin:end],
                            ab_prev=ab_prev[:, begin:end],
                            act=act[:, begin:end])
                if mx is not None:
                    args.update(mode=mx[0], cids=mx[1][lo:hi],
                                labels=mx[2][lo:hi])
                if sh is not None:
                    # the row-window layout (wave_window_specs):
                    # window rows shard over the host submesh's data
                    # axes, the wave-resident tables replicate onto
                    # that submesh
                    args = {k: jax.device_put(v, sh[k])
                            for k, v in args.items()}
                with self.tracer.span("segment.dispatch", host=w.host,
                                      rows=rows, begin=begin, end=end):
                    if mx is not None:
                        x = _window_segment_mixed(
                            params, self.dc, x, args["y"],
                            args["rk"], args["g"], args["ts"],
                            args["jloc"], args["ab_t"],
                            args["ab_prev"], args["act"],
                            mode=args["mode"], clf_ids=args["cids"],
                            labels=args["labels"], clf_fns=mx[3],
                            row_offset=lo,
                            image_size=self.image_size,
                            channels=self.channels, eta=self.eta,
                            use_pallas=self.use_pallas)
                    else:
                        x = _window_segment(
                            params, self.dc, x, args["y"],
                            args["rk"], args["g"], args["ts"],
                            args["jloc"], args["ab_t"],
                            args["ab_prev"], args["act"],
                            row_offset=lo,
                            image_size=self.image_size,
                            channels=self.channels, eta=self.eta,
                            use_pallas=self.use_pallas)
                prev = rows
        if self._sync_hook is not None:
            self._sync_hook("dispatch", w.host, wave)
        return jnp.clip(x, -1.0, 1.0)

    def _sample_wave_placed(self, parts_h, placement: WavePlacement, key,
                            max_steps: int, wave: int = -1):
        """Sample one placed wave, window-concurrently.

        Three phases.  PACK: each host's window packs on that host's
        worker (``_pack_window`` — rows, meta, per-window padding,
        activation-sorted when compaction is on so its epoch segments
        stay contiguous prefixes), overlapping other hosts' packs and
        device scans.  ASSEMBLE (sequential, cheap): splice the windows
        into ONE wave-resident set of per-row tables (``ragged_tables``
        over the whole wave) in window order.  DISPATCH: every window's
        jitted segment chain is enqueued — on its host's worker when the
        pool is live, back-to-back otherwise — before ANY fence, each
        reading the wave table at ``row_offset = window.offset``.
        Worker errors marshal back deterministically (``_collect``).

        Returns per-window device outputs (still in sorted order), the
        per-window inverse permutations, and per-window scheduled/active
        row-iteration counts.  Bit-identical with the pool on or off:
        packing/dispatch order never keys noise — row identity does."""
        pool = self._ensure_pool()
        wins = placement.windows
        # WAVE-level mixedness: one classifier-guided row anywhere makes
        # every window of the wave dispatch the mixed executable (windows
        # share the wave-resident tables; a mixed executable on pure-cfg
        # rows is the identical arithmetic bit-for-bit)
        mixed = any(p.req.mode == "clf"
                    for parts in parts_h for p, _, _ in parts)
        if pool is not None and all(w.host in pool.hosts for w in wins):
            packed = self._collect(
                [pool.submit(w.host, self._pack_window, w, parts_h[w.host],
                             max_steps, placement.total_rows, wave, mixed)
                 for w in wins])
        else:
            packed = [self._pack_window(w, parts_h[w.host], max_steps,
                                        placement.total_rows, wave, mixed)
                      for w in wins]
        win_rows = [p[0] for p in packed]
        win_meta = [p[1] for p in packed]
        win_inv = [p[2] for p in packed]
        win_plans = [p[3] for p in packed]
        host_stats = [p[4] for p in packed]
        meta_wave = [m for ms in win_meta for m in ms]
        cond = np.concatenate(win_rows)
        g = jnp.asarray([m[0] for m in meta_wave], jnp.float32)
        steps = np.array([m[1] for m in meta_wave], np.int32)
        row_keys = self._row_keys(meta_wave, key)
        ts, ab_t, ab_prev, jloc = ragged_tables(self.sched, steps, max_steps)
        act = jloc >= 0
        y = jnp.asarray(cond)
        # the mixed operands ride the ctx as one optional slot: mode is a
        # wave-resident table (read through row_offset like ab_t), the
        # classifier ids/labels are sliced per window like the cond rows
        mx = None
        if mixed:
            mx = (jnp.asarray([m[4] for m in meta_wave], jnp.float32),
                  np.array([m[5] for m in meta_wave], np.int32),
                  np.array([m[6] for m in meta_wave], np.int32),
                  tuple(self._clf_fns))
        ctx = (y, row_keys, g, ts, ab_t, ab_prev, jloc, act,
               placement.total_rows, mx)
        if pool is not None and all(w.host in pool.hosts for w in wins):
            xs = self._collect(
                [pool.submit(w.host, self._dispatch_window, w, epochs,
                             ctx, wave)
                 for w, epochs in zip(wins, win_plans)])
        else:
            xs = [self._dispatch_window(w, epochs, ctx, wave)
                  for w, epochs in zip(wins, win_plans)]
        return xs, win_inv, host_stats

    def _window_shardings(self, host: int) -> Optional[dict]:
        """Per-argument shardings for host ``host``'s window segments —
        the ``sharding/rules.py::wave_window_specs`` layout instantiated
        on the host's compute mesh (``HostTopology.host_mesh``), cached
        per host.  None for a simulated (mesh-less) topology: windows run
        wherever the local devices are."""
        if host in self._host_shardings:
            return self._host_shardings[host]
        sub = self.topology.host_mesh(host)
        sh = None
        if sub is not None:
            from repro.launch.mesh import mesh_axes
            from repro.sharding.rules import wave_window_specs
            specs = wave_window_specs(mesh_axes(sub))
            sh = {"y": NamedSharding(sub, specs["cond"]),
                  "rk": NamedSharding(sub, specs["row_keys"]),
                  "ts": NamedSharding(sub, specs["cond"]),
                  "jloc": NamedSharding(sub, specs["cond"]),
                  "g": NamedSharding(sub, specs["guidance"]),
                  "ab_t": NamedSharding(sub, specs["scalar_table"]),
                  "ab_prev": NamedSharding(sub, specs["scalar_table"]),
                  "act": NamedSharding(sub, specs["scalar_table"]),
                  "mode": NamedSharding(sub, specs["mode"]),
                  "cids": NamedSharding(sub, specs["clf_ids"]),
                  "labels": NamedSharding(sub, specs["labels"])}
        self._host_shardings[host] = sh
        return sh

    def _window_params(self, host: int):
        """The denoiser's parameters for host ``host``'s window segments:
        replicated onto the host's compute mesh once per topology and
        reused by every segment, so no dispatch moves them between
        devices.  The engine's own copy for a simulated topology."""
        sh = self._window_shardings(host)
        if sh is None:
            return self.dm_params
        if host not in self._host_params:
            self._host_params[host] = jax.device_put(
                self.dm_params, NamedSharding(sh["y"].mesh, P()))
        return self._host_params[host]

    def _fence_window(self, w, x, wave: int):
        """Fence ONE window's device output.  On a per-host worker the
        ``device.scan`` span measures that host's own device time — not
        another host's serialized wait, which is what the old in-order
        fence loop silently recorded for every window after the first."""
        with self.tracer.span("device.scan", host=w.host, wave=wave,
                              rows=w.rows):
            if self._sync_hook is not None:
                self._sync_hook("fence", w.host, wave)
            self._fence(x, host=w.host, wave=wave)

    def _retire_placed(self, st: "_DrainState", results, xs, invs,
                       placement: WavePlacement, parts_h, wave: int = -1):
        """Fence every window — on the per-host workers when the pool is
        live, so windows fence as they complete and a straggling host
        never serializes the others — then unsort compacted windows back
        to pack order, strip per-window padding, and scatter rows to
        requests in window order (delivery stays deterministic)."""
        pool = self._ensure_pool()
        wins = placement.windows
        if pool is not None and all(w.host in pool.hosts for w in wins):
            self._collect([pool.submit(w.host, self._fence_window, w, x,
                                       wave)
                           for w, x in zip(wins, xs)])
        else:
            for w, x in zip(wins, xs):
                self._fence_window(w, x, wave)
        with self.tracer.span("wave.retire", wave=wave):
            for w, x, inv in zip(placement.windows, xs, invs):
                arr = np.asarray(x)
                if inv is not None:
                    arr = arr[inv]
                outs = arr[:w.real]
                off = 0
                for p, t, _ in parts_h[w.host]:
                    p.chunks.append(outs[off:off + t])
                    off += t
                    if p.done_rows() == p.fresh:
                        self._finalize(st, p, results)

    def _retire(self, st: "_DrainState", results, x, parts, n_real,
                wave: int = -1):
        """Fence on the wave's device computation (``device.scan``), then
        (``wave.retire``) scatter rows back to their requests and finalize
        any request whose rows are complete."""
        with self.tracer.span("device.scan", host=0, wave=wave,
                              rows=int(x.shape[0])):
            self._fence(x, host=0, wave=wave)
        with self.tracer.span("wave.retire", wave=wave, host=0):
            outs = np.asarray(x)[:n_real]
            off = 0
            for p, t, _ in parts:
                p.chunks.append(outs[off:off + t])
                off += t
                if p.done_rows() == p.fresh:
                    self._finalize(st, p, results)

    def _finalize(self, st: "_DrainState", p: _Pending, results):
        self.tracer.stamp(p.req.rid, "retire")
        new = (np.concatenate(p.chunks) if p.chunks else
               np.zeros((0, self.image_size, self.image_size, self.channels),
                        np.float32))
        r = p.req
        if r.cache_key is not None:
            have = self._cache.get(r.cache_key)
            merged = new if have is None else np.concatenate([have, new])
            self._cache[r.cache_key] = merged
            # these rows moved from planned to cached — leaving them in
            # ``planned`` would double-count coverage for a same-key
            # request streamed in later this drain
            left = st.planned.get(r.cache_key, 0) - p.fresh
            st.planned[r.cache_key] = max(left, 0)
            if self.store is not None:
                self.store.put(r.cache_key, merged)
            st.deliver(results, r.rid, merged[:r.count].copy())
            self._serve_waiters(st, results)
        else:
            st.deliver(results, r.rid, new)

    def _serve_waiters(self, st: "_DrainState", results):
        still = []
        for r in st.waiters:
            cached = self._cache.get(r.cache_key)
            if cached is not None and len(cached) >= r.count:
                st.deliver(results, r.rid, cached[:r.count].copy())
            else:
                still.append(r)
        st.waiters = still


class _DrainState:
    """Book-keeping for one drain: live group queues, per-key rows already
    planned (cache top-up accounting), requests waiting on rows another
    request is generating, and the wave counter keying ``fold_in``."""

    def __init__(self):
        self.groups: dict[tuple, _GroupQueue] = {}
        self.planned: dict[tuple, int] = {}
        self.waiters: list[SynthesisRequest] = []
        self.admitted: set[int] = set()
        self.wave_i = 0
        self.started = False          # True once initial admission is done
        self.on_result = None         # this drain's streaming delivery hook
        self.on_error = None          # typed-failure delivery hook
        self.failed = {}              # rid -> RequestFailedError this drain
        self.tracer = None            # set by the engine at drain start

    def deliver(self, results: dict, rid: int, rows):
        if self.tracer is not None:
            self.tracer.stamp(rid, "deliver")
        results[rid] = rows
        if self.on_result is not None:
            self.on_result(rid, rows)
