"""A cell's measured window, traced with the program's own names in it.

    python3 benchmarks/chip/scoped_trace.py --workload b2.round_drain \\
        --seed 7 --seconds 17 --repeats 2 --out chiprun_out/scoped

On the chip the cell asks for, in one process: the cell's set-up and
warm-up as a run makes them, then ``--repeats`` pairs of measured
windows, every window under ``jax.profiler`` and on a fresh system made
from the same seed (so the same weights and requests, and no row served
from an earlier window's cache): the first of a pair with an enabled
``repro.obs.Tracer`` on the engine, so its spans are in the trace as
``synth.*``, the second without.  Each window's trace is reduced by
``tracescopes``, and one JSON line per window goes to standard output:
``images_per_s``, ``attention_core_share``, ``wave_host_ms``, the shares
of device time under a scope and of idle time under a span, ``by_scope_s``,
``idle_by_span_s``, the ten longest gaps by span, and the names of the
device programs that ran in the window.  ``--out`` receives the first
traced window's profile: the ``.xplane.pb`` and the trace JSON beside
it.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

STARTED = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
MODULES_LINE = "XLA Modules"


def programs(path: str, window) -> dict:
    """Device time per program (module) name inside the window."""
    from jax.profiler import ProfileData
    w0, w1 = window
    spent = Counter()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                c = min(s + d, w1) - max(s, w0)
                if c > 0:
                    spent[ev.name] += c / 1e9
    return dict(spent.most_common())


def window(cell, seed: int, seconds: float, traced: bool, family, dkey,
           out_dir: Path | None, log) -> dict:
    """One measured window on a fresh system, under the profiler."""
    import jax

    from benchmarks.chip import generator as traffic
    from benchmarks.chip import harness, tracereduce, tracescopes
    from repro.obs import Tracer
    wseed = harness.seed_keys(seed)[0]
    arrival = traffic.arrival(cell.traffic, cell.chip / "arrivals")
    system = family.System(cell.cfg, wseed, chips=cell.workload["chips"])
    svc = system.service(harness._service_class())
    svc.engine.opt_in(tracer=Tracer(enabled=traced))
    requests = traffic.generate(cell.traffic, seed, seconds,
                                cell.cfg["model"]["cond_dim"],
                                cell.chip / "arrivals")
    tdir = tempfile.mkdtemp(prefix="scoped_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    win_span = jax.profiler.TraceAnnotation("bench.window")
    stopped = []

    def close():
        if not stopped:
            stopped.append(True)
            win_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    win_span.__enter__()
    win = arrival.window(svc, system, requests, seconds, dkey,
                         on_close=close)
    close()
    t = time.perf_counter()
    path = tracereduce.find_xplane(tdir)
    if out_dir is not None:
        shutil.copytree(Path(path).parent, out_dir, dirs_exist_ok=True)
    doc = tracescopes.load(path)
    red = tracescopes.reduce(doc)
    line = {
        "seed": seed, "tracer": traced,
        "images_per_s": win["images"] / win["window_s"],
        "window_s": win["window_s"], "note": win["note"],
        "attention_core_share": tracescopes.attention_core_share(red),
        "wave_host_ms": tracescopes.wave_host_ms(red),
        "scoped_share": tracescopes.scoped_share(red),
        "named_idle_share": tracescopes.named_idle_share(red),
        "idle_share": red["idle_share"], "busy_s": red["busy_s"],
        "trace_window_s": red["window_s"],
        "by_scope_s": red["by_scope_s"],
        "idle_by_span_s": red["idle_by_span_s"],
        "top_gaps_by_span": red["top_gaps_by_span"],
        "top_gaps": red["top_gaps"], "wave_host_s": red["wave_host_s"],
        "spans_in_window": red["spans_in_window"],
        "by_category_s": red["by_category_s"], "top_ops": red["top_ops"],
        "programs_s": programs(path, tracereduce.window_of(doc)),
    }
    shutil.rmtree(tdir, ignore_errors=True)
    log(f"window reduced in {time.perf_counter() - t:.1f} s")
    system.free()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import generator as traffic
    from benchmarks.chip import harness
    cell = harness.load_cell(ROOT, args.workload)
    try:
        harness.accelerator(cell.workload["chips"])
    except harness.NoAccelerator as e:
        print(f"scoped_trace: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    family = harness.load_module(cell.chip / "families" /
                                 f"{cell.cfg['family']}.py")
    wseed, dkey, warm_key = harness.seed_keys(args.seed)
    system = family.System(cell.cfg, wseed, chips=cell.workload["chips"])
    svc = system.service(harness._service_class())
    traffic.arrival(cell.traffic, cell.chip / "arrivals").warm_up(
        system, svc, cell.traffic, [], warm_key,
        cell.cfg["model"]["cond_dim"])
    system.free()
    log(f"set-up and warm-up {time.perf_counter() - STARTED:.1f} s")
    out_dir = args.out
    for _ in range(args.repeats):
        for traced in (True, False):
            line = window(cell, args.seed, args.seconds, traced, family,
                          dkey, out_dir if traced else None, log)
            if traced:
                out_dir = None
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
