"""Helpers for the chip benchmark's CPU tests: a copy of the benchmark
whose configurations are cut to a tiny width, run through the same
harness functions as the chip path."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"d_model": 32, "num_layers": 2, "num_heads": 2,
              "latent_size": 8}

# the CPU has no row in peaks.json; shares read against this stand-in
CPU_PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_checkout(tmp: Path, *, wave_size: int = 20) -> Path:
    """A copy of ``BENCHMARK.json`` and ``benchmarks/chip`` under ``tmp``
    with every configuration cut to ``TINY_MODEL``."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for cfg in (tmp / "benchmarks" / "chip" / "configs").glob("*.json"):
        c = json.loads(cfg.read_text())
        c["model"].update(TINY_MODEL)
        c["engine"]["wave_size"] = wave_size
        cfg.write_text(json.dumps(c))
    return tmp


def run_cell(checkout: Path, workload: str, *, seed: int = 2 ** 31 + 5,
             seconds: float = 1.0, trace: bool = False, keep=None) -> dict:
    """One run of a cell of ``checkout`` on the CPU, quietly."""
    import jax

    from benchmarks.chip import harness
    cell = harness.load_cell(checkout, workload)
    return harness.run(cell, seed, seconds, trace, devices=jax.devices(),
                       peak=CPU_PEAK, log=lambda *a: None, keep=keep)
