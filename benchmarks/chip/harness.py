"""Runs one cell of the chip benchmark once.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

* the configuration file it names, whose ``family`` picks the module
  that assembles the system under test, ``families/<family>.py``, and
  whose ``reference`` picks the plain reference
  ``references/<reference>.py``;
* the traffic mix ``traffic/<traffic>.json``, read by ``generator.py``,
  whose ``arrival`` picks ``arrivals/<arrival>.py``;
* each metric's reader ``metrics/<name>.py`` (``setup_s`` is the
  harness's own);
* the limits of the correctness comparison, ``limits/<workload>.json``.

A run: set-up (weights from the seed, the service, every wave program the
traffic will dispatch compiled and run once), then the measured window
(``--seconds`` of traffic, profiled when ``--trace 1``), then the peak
device memory, then the check of the delivered rows against the plain
reference, then one JSON result line.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

clock = time.perf_counter


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    root: Path
    workload: dict
    cfg: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def chip(self) -> Path:
        return self.root / "benchmarks" / "chip"


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _covers(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    entry = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    cfg = json.loads((root / entry["file"]).read_text())
    chip = root / "benchmarks" / "chip"
    return Cell(root=root, workload=wl, cfg=cfg,
                traffic=json.loads((chip / "traffic" /
                                    f"{wl['traffic']}.json").read_text()),
                limits=json.loads((chip / "limits" /
                                   f"{workload}.json").read_text()),
                end_to_end=[m for m in bench["end_to_end"]
                            if _covers(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _covers(m, workload)])


def accelerator(chips: int):
    """The TPU devices the cell runs on."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    or at the fixed ``<checkout>/.jax_cache``, every program cached."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root.resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_keys(seed: int):
    """(weights seed, drain key, warm-up key) from a seed of any size."""
    import jax
    s = int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1))
    base = jax.random.PRNGKey(s)
    return s, jax.random.fold_in(base, 1), jax.random.fold_in(base, 2)


@contextlib.contextmanager
def _compile_log():
    """What JAX compiles inside the block (a program loaded from the
    persistent cache is announced the same way)."""
    import jax
    names = []

    class Grab(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                names.append(msg[len("Compiling "):][:160])

    handler, logger = Grab(level=logging.WARNING), logging.getLogger("jax")
    was = jax.config.jax_log_compiles
    logger.addHandler(handler)
    jax.config.update("jax_log_compiles", True)
    try:
        yield names
    finally:
        jax.config.update("jax_log_compiles", was)
        logger.removeHandler(handler)


def _service_class():
    """The program's service, with each delivery stamped on the host clock
    (and marked in the profiler's trace)."""
    import jax
    from repro.serve import SynthesisService

    class TimedService(SynthesisService):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.delivered: dict[int, float] = {}

        def _deliver(self, rid, rows):
            with jax.profiler.TraceAnnotation("bench.deliver"):
                self.delivered[rid] = clock()
                super()._deliver(rid, rows)

    return TimedService


# -- metrics ------------------------------------------------------------------

def read_metrics(cell: Cell, metrics: list, record: dict) -> dict:
    """Each metric's reader applied to the run's record; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        if m["name"] == "setup_s":
            continue
        reader = load_module(cell.chip / "metrics" / f"{m['name']}.py")
        v = reader.read(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# -- correctness --------------------------------------------------------------

def sample(win: dict, limits: dict, seed: int) -> list[tuple[int, int]]:
    """(rid, row index) pairs to compare: a row of the largest request
    delivered in the window, and rows drawn from the seed among all the
    rows of the others."""
    rng = np.random.default_rng([seed, 7])
    rids = sorted(win["in_window"])
    sub = win["submitted"]
    if not rids:
        return []
    big = max(rids, key=lambda r: (sub[r].images * sub[r].steps, -r))
    pool = [(r, i) for r in rids if r != big for i in range(sub[r].images)]
    n = min(limits["sample_rows"] - 1, len(pool))
    pick = [pool[j] for j in rng.choice(len(pool), n, replace=False)]
    return [(big, int(rng.integers(0, sub[big].images)))] + \
        [(int(r), int(i)) for r, i in pick]


def reference_rows(cell: Cell, win: dict, results: dict, params, drain_key,
                   seed: int) -> dict:
    """The sampled rows as served (``got``) and as the plain reference
    makes them (``want``), and as the reference makes them at the
    precision the configuration states (``stated``)."""
    ref = load_module(cell.chip / "references" /
                      f"{cell.cfg['reference']}.py")
    sub = win["submitted"]
    picks = [(r, i) for r, i in sample(win, cell.limits, seed)
             if r in results]
    rows = [dict(cond=sub[r].cond, guidance=sub[r].guidance,
                 steps=sub[r].steps, rid=r, index=i) for r, i in picks]
    out = {"rows": rows, "got": None, "want": None, "stated": None}
    if rows:
        kw = dict(T=cell.cfg["sampler"]["train_timesteps"],
                  block=cell.limits["reference_block"])
        out["got"] = np.stack([results[r][i] for r, i in picks])
        out["want"] = ref.sample_rows(params, cell.cfg["model"], rows,
                                      drain_key, **kw)
        out["stated"] = ref.sample_rows(params, cell.cfg["model"], rows,
                                        drain_key, **ref.STATED, **kw)
    return out


def judge(cell: Cell, win: dict, results: dict, got, want, stated) -> dict:
    """The numbers compared, each with its limit.  ``got`` are the rows
    put in the program's place: as served, or a control's."""
    sub = win["submitted"]
    bad = 0
    for rid, req in sub.items():
        rows = results.get(rid)
        if rows is None:
            continue
        if rows.shape[0] != req.images or not np.isfinite(rows).all() \
                or np.abs(rows).max() > 1.0:
            bad += 1
    missing = sum(1 for rid in sub if rid not in results)
    excess = 1e9 if got is None else gap_excess(got, want, stated)
    lim = cell.limits["limits"]
    return {
        "rows_missing": {"value": missing, "limit": 0},
        "rows_malformed": {"value": bad, "limit": 0},
        "row_gap_excess": {"value": excess, "limit": lim["row_gap_excess"]},
    }


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# the unit below which a row difference is float32 round-off: where the
# stated precision moves rows less (a backend that computes every float32
# product exactly, as the CPU does), gaps are read in this unit instead
GAP_FLOOR = 1e-3


def gap_excess(got, want, stated) -> float:
    """How much farther the served rows lie from the reference than the
    stated precision alone puts them: the geometric mean over rows of
    (mean |served − reference|) / (mean |stated − reference|), less 1.
    A 50-step guided trajectory amplifies a last-bit difference of one
    denoiser call a thousandfold, by an amount each row's noise and each
    seed's weights set; both sides of the ratio share that amount, so a
    program at the stated precision reads about 0 on every row."""
    g = np.maximum(row_gaps(got, want), GAP_FLOOR)
    s = np.maximum(row_gaps(stated, want), GAP_FLOOR)
    return float(np.exp(np.mean(np.log(g / s)))) - 1.0


def row_gaps(got, want) -> np.ndarray:
    """Mean |served − reference| of each row, in pixel units of [−1, 1]."""
    d = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    return d.reshape(len(d), -1).mean(axis=1)


# -- one run ------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        devices=None, peak=None, log=None, warm=True, keep=None,
        started=None) -> dict:
    """One run of ``cell``; returns the result line's object.  ``devices``
    defaults to the TPU chips the cell asks for (``NoAccelerator`` when
    they are not there) and ``peak`` to their row of ``peaks.json``.
    ``warm=False`` skips the warm-up, for a process that ran the same
    shapes before; ``keep`` (a dict) receives the compared rows and what
    they were made from.  ``started`` is when set-up began on the host
    clock (default: now); the command passes its own start, so set-up
    counts bringing JAX up on the chip."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    t_setup = clock() if started is None else started
    chips = cell.workload["chips"]
    if devices is None:
        devices = accelerator(chips)
    import jax

    from benchmarks.chip import cost, tracereduce
    from benchmarks.chip import generator as traffic
    arrival = traffic.arrival(cell.traffic, cell.chip / "arrivals")
    family = load_module(cell.chip / "families" /
                         f"{cell.cfg['family']}.py")
    phases = {"to_jax_on_chip": clock() - t_setup}
    wseed, dkey, warm_key = seed_keys(seed)
    system = family.System(cell.cfg, wseed, chips=chips, clock=clock)
    phases.update(system.phases)
    t = clock()
    cond_dim = cell.cfg["model"]["cond_dim"]
    requests = traffic.generate(cell.traffic, seed, seconds, cond_dim,
                                cell.chip / "arrivals")
    svc = system.service(_service_class())
    phases["traffic_and_service"] = clock() - t
    t = clock()
    if warm:
        arrival.warm_up(system, svc, cell.traffic, requests, warm_key,
                        cond_dim)
    phases["warm_up"] = clock() - t
    svc.delivered.clear()
    setup_s = clock() - t_setup
    log(f"set-up {setup_s} s {json.dumps(phases)}; {len(requests)} "
        f"requests in the mix")

    tdir = None
    if trace:
        import tempfile
        tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    win_span = jax.profiler.TraceAnnotation("bench.window")
    stopped = {"done": False}

    def close():
        if stopped["done"]:
            return
        stopped["done"] = True
        win_span.__exit__(None, None, None)
        if trace:
            jax.profiler.stop_trace()

    win_span.__enter__()
    with _compile_log() as compiled:
        win = arrival.window(svc, system, requests, seconds, dkey,
                             on_close=close)
    close()
    log(f"window {win['window_s']} s: {len(win['in_window'])} requests, "
        f"{win['images']} images; {win.get('note', '')}; {len(compiled)} "
        f"programs compiled while the window's requests were served "
        f"{json.dumps(compiled)}")

    results = {rid: f.result() for rid, f in win.pop("futures").items()
               if f.done() and f.exception() is None}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": int(mem_peak)}
    record = {"kind": win["kind"], "window": win, "trace": None,
              "chips": chips, "spec": cell.cfg["model"], "cost": cost,
              "tracereduce": tracereduce}
    out = {}
    if not trace:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"},
                   **read_metrics(cell, cell.end_to_end, record)}
    else:
        try:
            record["trace"] = tracereduce.reduce(tracereduce.load(
                tracereduce.find_xplane(tdir)))
        except FileNotFoundError:
            pass
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        record["peak"] = peak or cost.peaks(devices[0].device_kind)
        metrics = read_metrics(cell, cell.per_layer, record)
        red = record["trace"]
        if red is not None:
            dev["busy_s"] = red["busy_s"]
            dev["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["top_ops"],
                                "idle_gaps": red["top_gaps"]}
            log("per-chip idle share: " + json.dumps(
                {k: v["idle_share"] for k, v in red["per_chip"].items()}))
            log("device time by category (s): "
                + json.dumps(red["by_category_s"]))
    params = system.params
    system.free()
    del svc
    rows = reference_rows(cell, win, results, params, dkey, seed)
    checks = judge(cell, win, results, rows["got"], rows["want"],
                   rows["stated"])
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    attempted = len(win["submitted"])
    if keep is not None:
        keep.update(rows, params=params, drain_key=dkey, cell=cell,
                    window=win, results=results)
    return {"correct": passes(checks), "attempted": attempted,
            "failed": attempted - len(results), "metrics": metrics,
            "device": dev, **out, "checks": checks}
