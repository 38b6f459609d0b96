"""Rehearsal of the chip benchmark on the CPU at a tiny width: the cell
and every metric reader run end to end through the harness functions the
chip path uses; a metric or an arrival process is added as files alone;
and the command refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_util import ROOT, run_cell, tiny_checkout

from benchmarks.chip import harness


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("bench"))


def test_cell_runs_end_to_end(checkout):
    out = run_cell(checkout, "b2.round_drain")
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    cell = harness.load_cell(checkout, "b2.round_drain")
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["metrics"]["images_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert list(out["checks"]) == ["rows_missing", "rows_malformed",
                                   "row_gap_excess"]
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reads_every_metric_it_can(checkout):
    """No device plane on the CPU: the trace readers return nothing and
    are left out; the counter reader reports."""
    out = run_cell(checkout, "b2.round_drain", trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"denoiser_mfu"}
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_metric_added_as_a_file(checkout):
    """A new per-layer metric is one reader file and one entry."""
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "waves_per_s.drain", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "images_per_s", "workloads": ["b2.round_drain"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    reader = checkout / "benchmarks" / "chip" / "metrics" / \
        "waves_per_s.drain.py"
    reader.write_text(
        "def read(rec):\n"
        "    w = rec['window']\n"
        "    return w['counters']['waves'] / w['window_s']\n")
    try:
        out = run_cell(checkout, "b2.round_drain", trace=True)
    finally:
        reader.unlink()
        bench["per_layer"].pop()
        (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    assert out["metrics"]["waves_per_s.drain"]["value"] > 0


def test_arrival_process_added_as_files(tmp_path):
    """A new kind of arrival is one module, one traffic file and one cell
    entry: here a round whose requests each ask for half the images."""
    checkout = tiny_checkout(tmp_path)
    chip = checkout / "benchmarks" / "chip"
    src = (chip / "arrivals" / "backlog.py").read_text()
    (chip / "arrivals" / "half_backlog.py").write_text(src.replace(
        "int(traffic[\"images\"])", "int(traffic[\"images\"]) // 2"))
    t = json.loads((chip / "traffic" / "round_drain.json").read_text())
    t["arrival"] = "half_backlog"
    (chip / "traffic" / "half_round.json").write_text(json.dumps(t))
    shutil.copy(chip / "limits" / "b2.round_drain.json",
                chip / "limits" / "b2.half_round.json")
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    bench["workloads"].append(dict(bench["workloads"][0],
                                   name="b2.half_round",
                                   traffic="half_round"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("b2.half_round")
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    keep = {}
    out = run_cell(checkout, "b2.half_round", keep=keep)
    assert out["correct"] is True
    assert {r.images for r in keep["window"]["submitted"].values()} == {5}


def test_command_refuses_without_a_tpu():
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "b2.round_drain", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_command_refuses_without_the_program(tmp_path):
    """A directory with only the benchmark's own files runs nothing."""
    checkout = tiny_checkout(tmp_path)
    r = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "b2.round_drain", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=checkout, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
