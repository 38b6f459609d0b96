"""The chip benchmark's one command: run one cell once.

    python3 benchmarks/chip/run.py --workload b2.round_drain --seed 7 \\
        --seconds 50 --trace 0

Run it from the root of a checkout, on a machine whose JAX sees the TPU
chips the cell asks for.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; last in it,
``checks``, each number compared with its limit.  The same checks are
the last lines of standard error.  Without a TPU, or without the program
beside the benchmark, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

STARTED = time.perf_counter()

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness
    cell = harness.load_cell(ROOT, args.workload)
    try:
        harness.accelerator(cell.workload["chips"])
        import repro  # noqa: F401 - the system under test must be here
    except (harness.NoAccelerator, ImportError) as e:
        print(f"chip benchmark: {e}; nothing was run", file=sys.stderr)
        return 2
    print("compile cache: " + harness.enable_compile_cache(ROOT),
          file=sys.stderr, flush=True)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         started=STARTED)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
