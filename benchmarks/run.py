"""Benchmark aggregator — one harness per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--preset quick|paper]
                                            [--only table1,table4,...]

Presets: ``paper`` (default) mirrors the paper's experiment scale within
the CPU budget (~30–45 min, DM pre-trained once and cached); ``quick``
is a minutes-scale smoke of every harness.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.utils import enable_compile_cache

ALL = ("kernels", "synthesis", "table4", "roofline", "table1", "table2",
       "table3", "fig1", "guidance", "dropout")


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default=os.environ.get("REPRO_BENCH_PRESET",
                                                       "paper"))
    ap.add_argument("--only", default=None,
                    help="comma list out of: " + ",".join(ALL))
    args = ap.parse_args()
    which = args.only.split(",") if args.only else list(ALL)

    t0 = time.perf_counter()
    print(f"== repro benchmarks (preset={args.preset}) ==", flush=True)

    table1_res = None
    if "kernels" in which:
        from benchmarks import kernels_bench
        kernels_bench.run()
    if "synthesis" in which:
        from benchmarks import synthesis_throughput
        synthesis_throughput.run(args.preset)
    if "table4" in which:
        from benchmarks import table4_communication
        table4_communication.run(args.preset)
    if "roofline" in which:
        from benchmarks import roofline
        roofline.main()
    if "table1" in which:
        from benchmarks import table1_main
        table1_res = table1_main.run(args.preset)
    if "table2" in which:
        from benchmarks import table2_classifiers
        table2_classifiers.run(args.preset)
    if "table3" in which:
        from benchmarks import table3_sample_count
        counts = (10, 20, 30) if args.preset == "quick" else (10, 20, 30, 40, 50)
        table3_sample_count.run(args.preset, counts=counts)
    if "fig1" in which:
        from benchmarks import fig1_comm_vs_acc
        fig1_comm_vs_acc.run(args.preset, table1=table1_res)
    if "guidance" in which:
        from benchmarks import guidance_sweep
        scales = (0.0, 2.0, 7.5) if args.preset == "quick" else guidance_sweep.SCALES
        guidance_sweep.run(args.preset, scales=scales)
    if "dropout" in which:
        from benchmarks import dropout_robustness
        rates = (1.0, 0.5) if args.preset == "quick" else dropout_robustness.RATES
        dropout_robustness.run(args.preset, rates=rates)

    print(f"\n== done in {time.perf_counter()-t0:.0f}s ==")


if __name__ == "__main__":
    main()
