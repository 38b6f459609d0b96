"""From a profiler trace to the device numbers the benchmark reports.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain dict: the text of each distinct XLA op that ran on a TPU (its HLO
instruction: name, result shape, operands and fusion kind), for every
TPU device plane its op events as ``[op index, start, duration]`` in ns
on the profiler's clock, and the benchmark's own host spans (``bench.*``
``TraceAnnotation``s).  ``reduce`` turns that dict into:

* per chip, the busy time: the union of the op intervals inside the
  window, so overlapping ops count once; the idle share is 1 − busy /
  window;
* device time by op category and by op.  A TPU trace carries no category
  stat, so the category comes from the HLO text: a fusion of kind
  ``kOutput`` or named for a convolution is a ``convolution fusion`` (how
  XLA runs a dot on the TPU), other fusions go by kind, the rest by
  opcode.  Control flow (``while``, ``conditional``, ``call``) contains
  the ops it runs, so it counts towards busy time and towards no op;
* the idle gaps, each labelled by the benchmark span that covers most of
  it (what the host was doing while the chip waited).

The window is the ``bench.window`` span when the trace has one.  The
dict is what ``tests/data`` keeps of a recorded trace, so the reduction
is checked on a real one.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# spans that cover the whole window: a gap under no other span is the
# engine's own host work between waves
OUTER_SPANS = (WINDOW_SPAN, "bench.drain")
CONTAINERS = ("while", "conditional", "call")
IDLE_UNDER_NO_SPAN = "engine host work"

_HEAD = re.compile(r"%([A-Za-z][\w\-]*?)(?:\.\d+)*\s*=")
_KIND = re.compile(r"kind=k(\w+)")


def opcode(text: str) -> str:
    """The instruction's name without its number: ``fusion``,
    ``convolution_add_fusion``, ``copy-start``, ``while``."""
    m = _HEAD.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def category(text: str) -> str:
    head = opcode(text)
    if head in CONTAINERS:
        return "control flow"
    kind = _KIND.search(text)
    if head.endswith("fusion"):
        if "convolution" in head or (kind and kind.group(1) == "Output"):
            return "convolution fusion"
        return f"{kind.group(1).lower() if kind else 'other'} fusion"
    if head.startswith("convolution") or head == "dot":
        return "convolution fusion"
    return head


def short(text: str) -> str:
    """``opcode result-shape`` of an op's text: the same op of every
    unrolled layer reads alike, so reports sum them."""
    _, _, rhs = text.partition(" = ")
    shape = rhs.split("{", 1)[0].split(" ", 1)[0]
    return f"{opcode(text)} {shape}" if rhs else text[:80]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """The device op events and benchmark spans of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    texts, index, devices, spans = [], {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[
                len(DEVICE_PREFIX):].isdigit():
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    i = index.setdefault(ev.name, len(texts))
                    if i == len(texts):
                        texts.append(ev.name)
                    ops.append([i, int(ev.start_ns), int(ev.duration_ns)])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {"ops": texts, "devices": devices, "spans": spans}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(doc: dict):
    """(start, end) ns of the traced window: the ``bench.window`` span,
    else the extent of the device ops."""
    for name, s, d in doc["spans"]:
        if name == WINDOW_SPAN:
            return s, s + d
    ops = [o for ops in doc["devices"].values() for o in ops]
    if not ops:
        return None
    return (min(o[1] for o in ops), max(o[1] + o[2] for o in ops))


def _label(gap, spans) -> str:
    """The benchmark span (other than the outer ones) covering most of
    the gap; a gap under none is the engine's own host work."""
    best, cover = IDLE_UNDER_NO_SPAN, 0
    for name, s, d in spans:
        if name in OUTER_SPANS:
            continue
        c = min(gap[1], s + d) - max(gap[0], s)
        if c > cover:
            best, cover = name, c
    return best


def reduce(doc: dict, top: int = 10) -> dict | None:
    """Busy and idle time per chip, device time by category and op, and
    the longest idle gaps with their labels.  None when the trace holds
    no device op."""
    win = window_of(doc)
    if win is None or not any(doc["devices"].values()):
        return None
    w0, w1 = win
    window_ns = w1 - w0
    texts = doc["ops"]
    cats = [category(t) for t in texts]
    per_chip, by_cat, by_op, gaps = {}, defaultdict(int), defaultdict(int), []
    for dev, ops in sorted(doc["devices"].items()):
        clipped = []
        for i, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            if cats[i] != "control flow":
                by_cat[cats[i]] += b - a
                by_op[i] += b - a
        busy = _union(clipped)
        busy_ns = sum(e - s for s, e in busy)
        per_chip[dev] = {"busy_s": busy_ns / 1e9,
                         "idle_share": 1.0 - busy_ns / window_ns}
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label((s, e), doc["spans"]), dev))
    chips = len(per_chip)
    gaps.sort(reverse=True)
    by_label = defaultdict(float)
    for g, label, _ in gaps:
        by_label[label] += g / 1e9
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])
    by_short = defaultdict(int)
    for i, v in ranked:
        by_short[short(texts[i])] += v
    return {
        "window_s": window_ns / 1e9,
        "chips": chips,
        "per_chip": per_chip,
        "busy_s": sum(c["busy_s"] for c in per_chip.values()) / chips,
        "idle_share": sum(c["idle_share"] for c in per_chip.values()) / chips,
        "by_category_s": {k: v / 1e9 for k, v in sorted(by_cat.items())},
        "by_op_s": {texts[i]: v / 1e9 for i, v in ranked},
        "top_ops": [[n, v / 1e9] for n, v in sorted(
            by_short.items(), key=lambda kv: -kv[1])[:top]],
        "top_gaps": [[label, g / 1e9] for g, label, _ in gaps[:top]],
        "idle_by_label_s": dict(sorted(by_label.items(),
                                       key=lambda kv: -kv[1])),
    }
