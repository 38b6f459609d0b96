"""The program's own names in a profiler trace: the denoiser's scopes and
the engine's spans, read beside what ``tracereduce`` reads.

``load`` reads a ``.xplane.pb`` into ``tracereduce``'s dict (``ops``,
``devices`` and the ``bench.*`` ``spans``, the same values) and adds:

* ``scopes``, parallel to ``ops``: each op's innermost named scope, the
  last component of its ``op_name`` that starts with ``dit.`` (a sub-block
  of the denoiser) or ``sampler.`` (the scan body's own work), or None.
  The ``op_name`` is the HLO instruction's metadata, which a TPU trace
  carries as each op's framework-op stat (``tf_op``) in its event
  metadata.  ``ProfileData`` does not show event metadata, so the stat
  is read from the ``<host>.trace.json.gz`` the profiler writes beside
  the ``.xplane.pb`` (the same events, with their metadata as
  arguments), matched by the op's HLO text.  XLA gives a fusion its
  root instruction's metadata, so a fusion takes its root's scope.  An
  op XLA inserted itself carries none (a relayout copy, a prefetch of
  weights into fast memory; the profiler names it by the loop around
  it), so it takes the scope of the first op of its program that reads
  its result: the copy that lays out k for the score product counts as
  attention, as the same relayout inside the fused kernel's wrapper
  does;
* ``host_spans``: ``[name, start, duration, args, line]`` of every host
  span that labels what the host did: the benchmark's ``bench.*``, the
  engine's ``synth.*`` (an enabled ``repro.obs.Tracer``, its attributes
  as ``args``) and JAX's compile work, named ``compile``.  ``line`` is the
  host thread's line, so nesting is read per thread.

``reduce`` returns ``tracereduce.reduce``'s dict, every key as it reads
it there, plus:

* ``by_scope_s``: device time in the window per scope, ops under none as
  ``unscoped`` (control flow counts towards no scope, as towards no op);
* ``spans_in_window``: per host span name, the count and seconds of its
  spans inside the window;
* ``idle_by_span_s`` and ``top_gaps_by_span``: the window's idle time
  split by the innermost host span over each instant (the shortest that
  covers it; the outer spans ``bench.window``, ``bench.drain`` and
  ``synth.drain`` do not count), time under none as ``engine host work``;
* ``wave_host_s``: per wave dispatched in the window whose admission,
  pack, dispatch and retire spans are all in the trace, the self time
  (less nested spans, such as the load generator's ``bench.poll``) of
  those four spans: the scheduler's own host work for the wave.

``attention_core_share`` and ``wave_host_ms`` read that reduction.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from bisect import bisect_right
from collections import defaultdict

from benchmarks.chip import tracereduce

SCOPE_PREFIXES = ("dit.", "sampler.")
SCOPE_STAT = "tf_op"
UNSCOPED = "unscoped"
ATTENTION_CORE = "dit.attn.core"
SYNTH_PREFIX = "synth."
# JAX's own host annotations around lowering and compiling a program
COMPILE_PREFIXES = ("backend_compile", "lower_sharding_computation")
COMPILE = "compile"
OUTER_SPANS = tracereduce.OUTER_SPANS + ("synth.drain",)
WAVE_HOST_SPANS = tuple(SYNTH_PREFIX + n for n in (
    "wave.admit", "wave.pack", "wave.dispatch", "wave.retire"))
DISPATCH_SPAN = SYNTH_PREFIX + "wave.dispatch"


def scope_of(op_name: str | None) -> str | None:
    """The innermost program scope in an ``op_name`` path."""
    for part in reversed((op_name or "").split("/")):
        if part.startswith(SCOPE_PREFIXES):
            return part
    return None


def op_names(path: str) -> dict:
    """HLO text → ``op_name`` of the device ops in the trace JSON the
    profiler wrote beside ``path`` (empty where it wrote none)."""
    out = {}
    for js in glob.glob(os.path.join(os.path.dirname(path),
                                     "*.trace.json.gz")):
        with gzip.open(js, "rt") as f:
            events = json.load(f).get("traceEvents", [])
        for ev in events:
            args = ev.get("args") or {}
            if "long_name" in args and SCOPE_STAT in args:
                out.setdefault(args["long_name"], args[SCOPE_STAT])
    return out


_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")


def _inherit_scopes(texts, scopes, programs) -> list:
    """Each op with no scope takes the scope of the first op of its
    program that reads its result, through chains of such ops (a
    prefetch's start, its done, the fusion that reads it)."""
    reader = {}
    for j, t in enumerate(texts):
        for operand in _OPERAND.findall(t.partition(" = ")[2]):
            reader.setdefault((programs[j], operand), j)
    out = list(scopes)
    changed = True
    while changed:
        changed = False
        for i, t in enumerate(texts):
            j = reader.get((programs[i], t.partition(" = ")[0][1:]))
            if out[i] is None and programs[i] and j is not None \
                    and out[j] is not None:
                out[i], changed = out[j], True
    return out


def _host_name(name: str) -> str | None:
    if name.startswith((tracereduce.SPAN_PREFIX, SYNTH_PREFIX)):
        return name
    if name.startswith(COMPILE_PREFIXES):
        return COMPILE
    return None


def _arg(v):
    try:
        return int(v)
    except (TypeError, ValueError):
        return v if isinstance(v, (int, float)) else str(v)


def load(path: str) -> dict:
    """The device op events with their scopes, and the host spans, of one
    trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    names = op_names(path)
    texts, scopes, index, devices = [], [], {}, {}
    spans, host_spans = [], []
    line_no = 0
    for plane in pd.planes:
        if plane.name.startswith(tracereduce.DEVICE_PREFIX) and plane.name[
                len(tracereduce.DEVICE_PREFIX):].isdigit():
            ops = []
            for line in plane.lines:
                if line.name != tracereduce.OPS_LINE:
                    continue
                for ev in line.events:
                    i = index.setdefault(ev.name, len(texts))
                    if i == len(texts):
                        texts.append(ev.name)
                        scopes.append(names.get(ev.name))
                    ops.append([i, int(ev.start_ns), int(ev.duration_ns)])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                line_no += 1
                for ev in line.events:
                    name = _host_name(ev.name)
                    if name is None:
                        continue
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    if name.startswith(tracereduce.SPAN_PREFIX):
                        spans.append([name, s, d])
                    args = ({k: _arg(v) for k, v in ev.stats}
                            if name.startswith(SYNTH_PREFIX) else {})
                    host_spans.append([name, s, d, args, line_no])
    programs = [n.split("/", 1)[0] if n else None for n in scopes]
    scopes = _inherit_scopes(texts, [scope_of(n) for n in scopes], programs)
    return {"ops": texts, "devices": devices, "spans": spans,
            "scopes": scopes, "host_spans": host_spans}


def _innermost_segments(spans):
    """The timeline cut where any span starts or ends, each piece
    labelled by the shortest span over it (None under none)."""
    cuts = sorted({t for _, s, d in spans for t in (s, s + d)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(d, name) for name, s, d in spans if s <= a and s + d >= b]
        out.append((a, b, min(over)[1] if over else None))
    return out


def _idle_by_span(gaps, spans):
    """Per gap, seconds under each innermost span (``engine host work``
    where none covers)."""
    segs = _innermost_segments(spans)
    starts = [a for a, _, _ in segs]
    out = []
    for g0, g1 in gaps:
        parts, covered = defaultdict(int), 0
        k = max(bisect_right(starts, g0) - 1, 0)
        while k < len(segs) and segs[k][0] < g1:
            a, b, name = segs[k]
            c = min(b, g1) - max(a, g0)
            if c > 0 and name is not None:
                parts[name] += c
                covered += c
            k += 1
        if g1 - g0 > covered:
            parts[tracereduce.IDLE_UNDER_NO_SPAN] += g1 - g0 - covered
        out.append(parts)
    return out


def _self_times(host_spans):
    """Each host span's duration less its direct children's, nesting read
    per thread line."""
    own = [d for _, _, d, _, _ in host_spans]
    by_line = defaultdict(list)
    for k, (_, s, d, _, line) in enumerate(host_spans):
        by_line[line].append((s, -d, k))
    for items in by_line.values():
        stack = []                       # (end, index) of open spans
        for s, neg_d, k in sorted(items):
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                own[stack[-1][1]] -= -neg_d
            stack.append((s - neg_d, k))
    return own


def reduce(doc: dict, top: int = 10) -> dict | None:
    """``tracereduce.reduce`` of ``doc``, with the scopes and host spans
    read beside it (the keys in the module docstring)."""
    red = tracereduce.reduce(doc, top)
    if red is None:
        return None
    w0, w1 = tracereduce.window_of(doc)
    texts, scopes = doc["ops"], doc.get("scopes") or [None] * len(doc["ops"])
    by_scope, gaps = defaultdict(int), []
    for ops in doc["devices"].values():
        clipped = []
        for i, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            if tracereduce.category(texts[i]) != "control flow":
                by_scope[scopes[i] or UNSCOPED] += b - a
        edges = ([w0] + [x for iv in tracereduce._union(clipped) for x in iv]
                 + [w1])
        gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    host = doc.get("host_spans", [])
    labels = [[n, s, d] for n, s, d, _, _ in host if n not in OUTER_SPANS]
    split = _idle_by_span(gaps, labels)
    idle = defaultdict(int)
    for parts in split:
        for name, t in parts.items():
            idle[name] += t
    ranked = sorted(zip(gaps, split), key=lambda gp: gp[0][0] - gp[0][1])
    in_window = defaultdict(lambda: [0, 0.0])
    for n, s, d, _, _ in host:
        c = min(s + d, w1) - max(s, w0)
        if c > 0 or (d == 0 and w0 <= s <= w1):
            in_window[n][0] += 1
            in_window[n][1] += max(c, 0) / 1e9
    own = _self_times(host)
    waves = defaultdict(dict)
    for k, (n, s, d, args, _) in enumerate(host):
        if n in WAVE_HOST_SPANS and "wave" in args:
            w = waves[args["wave"]]
            w[n] = w.get(n, 0) + own[k]
            if n == DISPATCH_SPAN:
                w["dispatched_at"] = s
    wave_host = {w: sum(v[n] for n in WAVE_HOST_SPANS) / 1e9
                 for w, v in sorted(waves.items())
                 if all(n in v for n in WAVE_HOST_SPANS)
                 and w0 <= v["dispatched_at"] <= w1}
    red.update({
        "by_scope_s": {k: v / 1e9 for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "spans_in_window": {k: v for k, v in sorted(in_window.items())},
        "idle_by_span_s": {k: v / 1e9 for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])},
        "top_gaps_by_span": [[max(p, key=p.get), (g1 - g0) / 1e9]
                             for (g0, g1), p in ranked[:top]],
        "wave_host_s": wave_host,
    })
    return red


# -- the two per-layer readings of a reduction --------------------------------

def attention_core_share(red: dict | None) -> float | None:
    """Device time of the ops under ``dit.attn.core`` over the busy
    device time of the window, in %: the attention core's share whatever
    implements it (the naive score/softmax/PV chain or one kernel)."""
    if not red or not red.get("by_scope_s"):
        return None
    core = red["by_scope_s"].get(ATTENTION_CORE)
    busy = red["busy_s"] * red["chips"]
    if core is None or busy <= 0:
        return None
    return 100.0 * core / busy


def wave_host_ms(red: dict | None) -> float | None:
    """Mean over the window's waves of the scheduler's own host work per
    wave (admission, pack, dispatch and retire, self time), in ms."""
    waves = (red or {}).get("wave_host_s")
    if not waves:
        return None
    return 1e3 * sum(waves.values()) / len(waves)


def scoped_share(red: dict) -> float:
    """Share of the window's device op time that lies under a scope."""
    total = sum(red["by_scope_s"].values())
    return 1.0 - red["by_scope_s"].get(UNSCOPED, 0.0) / total if total else 0.0


def named_idle_share(red: dict) -> float:
    """Share of the window's idle time that lies under a named span."""
    total = sum(red["idle_by_span_s"].values())
    none = red["idle_by_span_s"].get(tracereduce.IDLE_UNDER_NO_SPAN, 0.0)
    return 1.0 - none / total if total else 1.0
