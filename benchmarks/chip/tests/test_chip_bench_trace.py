"""The trace reduction: busy/idle union, device time by category and the
labels of idle gaps, on a hand-made trace and on one recorded on a
TPU v5e (``data/trace_b2_round_drain.json``: a stretch of a traced
``b2.round_drain`` window, reduced to its op events and benchmark
spans by ``tracereduce.load``)."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench_util import ROOT  # noqa: F401 - puts the repo on sys.path

from benchmarks.chip import tracereduce

DATA = Path(__file__).resolve().parent / "data" / "trace_b2_round_drain.json"


TEXTS = [
    "%fusion.1 = f32[240,257,768]{2,0,1} fusion(f32[240,257,3072] %a, "
    "bf16[3072,768] %w), kind=kOutput, calls=%fused_computation.79",
    "%fusion.2 = f32[240,12,257]{2,1,0} fusion(f32[240,12,257,257] %b), "
    "kind=kLoop, calls=%fused_computation.60",
    "%convolution_add_fusion.3 = f32[240,4608]{1,0} fusion(bf16[240,768] "
    "%c, bf16[768,4608] %w), kind=kOutput, calls=%fused_computation.493",
    "%copy.4 = f32[120,32,32,4]{0,2,3,1} copy(f32[120,32,32,4] %x)",
    "%while.3 = (s32[], f32[120,32,32,4]) while((s32[], f32[120,32,32,4]) "
    "%tuple), condition=%cond, body=%body",
]


def _doc():
    # one chip; ns.  Ops overlap at [30, 40); gaps [0,10) [60,70) [90,95)
    return {"ops": TEXTS, "devices": {"/device:TPU:0": [
        [0, 10, 30],      # [10, 40)
        [1, 30, 30],      # [30, 60)
        [2, 70, 20],      # [70, 90)
        [3, 95, 20],      # [95, 115) clipped to the window
        [4, 72, 10]]},    # a loop around [72, 82): busy, but no op's time
        "spans": [["bench.window", 0, 100], ["bench.drain", 0, 100],
                  ["bench.poll", 55, 20], ["bench.deliver", 88, 10]]}


def test_categories_from_hlo_text():
    assert [tracereduce.category(t) for t in TEXTS] == [
        "convolution fusion", "loop fusion", "convolution fusion", "copy",
        "control flow"]
    assert tracereduce.short(TEXTS[0]) == "fusion f32[240,257,768]"


def test_hand_made_trace():
    red = tracereduce.reduce(_doc())
    assert red["window_s"] == pytest.approx(100e-9)
    # busy = [10, 60) + [70, 90) + [95, 100) = 75 ns
    assert red["busy_s"] == pytest.approx(75e-9)
    assert red["idle_share"] == pytest.approx(0.25)
    assert red["by_category_s"] == pytest.approx(
        {"convolution fusion": 50e-9, "loop fusion": 30e-9, "copy": 5e-9})
    gaps = {(label, round(s * 1e9)) for label, s in red["top_gaps"]}
    assert gaps == {("engine host work", 10), ("bench.poll", 10),
                    ("bench.deliver", 5)}


def test_no_device_ops_reads_nothing():
    assert tracereduce.reduce({"devices": {}, "spans": []}) is None


def _grid_busy(ops, w0, w1):
    """Busy ns by marking a 1-ns grid: an independent union."""
    grid = np.zeros(w1 - w0, bool)
    for _, s, d in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            grid[a - w0:b - w0] = True
    return int(grid.sum())


def test_recorded_trace():
    doc = json.loads(DATA.read_text())
    red = tracereduce.reduce(doc)
    w0, w1 = tracereduce.window_of(doc)
    (dev, ops), = doc["devices"].items()
    busy = _grid_busy(ops, w0, w1)
    assert red["busy_s"] == pytest.approx(busy / 1e9, abs=1e-9)
    assert red["idle_share"] == pytest.approx(1 - busy / (w1 - w0), abs=1e-9)
    # every op's clipped time but the loop's lands in exactly one category
    loops = {i for i, t in enumerate(doc["ops"])
             if tracereduce.category(t) == "control flow"}
    clipped = sum(max(0, min(s + d, w1) - max(s, w0))
                  for i, s, d in ops if i not in loops)
    assert sum(red["by_category_s"].values()) == pytest.approx(clipped / 1e9)
    # the scan's matmuls: most of the device time is convolution fusions
    conv = red["by_category_s"]["convolution fusion"]
    assert conv > 0.5 * sum(red["by_category_s"].values())
    # idle time is fully attributed to labels
    assert sum(red["idle_by_label_s"].values()) == pytest.approx(
        (w1 - w0 - busy) / 1e9, abs=1e-9)
