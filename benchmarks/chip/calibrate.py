"""Readings that the correctness limits of a cell are set from.

    python3 benchmarks/chip/calibrate.py --workload b2.round_drain \\
        --seeds 101-112 --seconds 1

In one process, on the chip the cell asks for, for each seed: one run of
the cell's timed path (with a short window) whose sampled rows are
judged against the plain reference as a run judges them; then the
control, the reference in bfloat16 (``references/<name>.py``'s
``CONTROL``) put in the program's place on the same rows and judged by
the same function; then the served rows handed to the wrong requests
(shifted by one within the sample), the answer altered where it is
produced.  One JSON line per seed on standard output, with whether each
side came out correct; the summary last.  The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def seeds(text: str) -> list[int]:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--dump", default=None,
                    help="directory for each seed's compared rows (.npz)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness
    cell = harness.load_cell(ROOT, args.workload)
    try:
        harness.accelerator(cell.workload["chips"])
    except harness.NoAccelerator as e:
        print(f"calibrate: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    print(json.dumps(readings(cell, args.seeds, args.seconds, args.dump)),
          flush=True)
    return 0


def readings(cell, seed_list, seconds, dump=None, run_kw=None) -> dict:
    """Program, control and fault readings of ``row_gap_excess`` on each
    seed; ``run_kw`` passes devices and peak to ``harness.run`` off the
    chip."""
    import numpy as np

    from benchmarks.chip import harness
    ref = harness.load_module(cell.chip / "references" /
                              f"{cell.cfg['reference']}.py")
    sides = {"program": [], "control": [], "fault": []}
    for n, seed in enumerate(seed_list):
        keep = {}
        out = harness.run(cell, seed, seconds, False, warm=n == 0,
                          keep=keep, **(run_kw or {}))
        control = ref.sample_rows(
            keep["params"], cell.cfg["model"], keep["rows"],
            keep["drain_key"], T=cell.cfg["sampler"]["train_timesteps"],
            block=cell.limits["reference_block"], **ref.CONTROL)
        fault = np.roll(keep["got"], 1, axis=0)
        line = {"seed": seed, "rows": len(keep["rows"]),
                "metrics": out["metrics"]}
        for side, got in (("program", keep["got"]), ("control", control),
                          ("fault", fault)):
            checks = harness.judge(cell, keep["window"], keep["results"],
                                   got, keep["want"], keep["stated"])
            v = checks["row_gap_excess"]["value"]
            sides[side].append(v)
            line[side] = {"row_gap_excess": v,
                          "correct": harness.passes(checks)}
        if dump:
            Path(dump).mkdir(parents=True, exist_ok=True)
            np.savez_compressed(
                Path(dump) / f"{cell.workload['name']}_{seed}.npz",
                got=keep["got"], want=keep["want"], stated=keep["stated"],
                control=control)
        print(json.dumps(line), flush=True)
    return {"workload": cell.workload["name"],
            "limit": cell.limits["limits"]["row_gap_excess"],
            "program_max": max(sides["program"]),
            "control_min": min(sides["control"]),
            "fault_min": min(sides["fault"]), **sides}


if __name__ == "__main__":
    sys.exit(main())
