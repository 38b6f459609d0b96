"""A federation round waiting to be drained.

The traffic file gives ``clients``, ``categories``, ``images``, ``steps``
and ``guidance``: one request per (client, category) in upload order,
each for ``images`` rows of that category's encoding at one guidance
scale and step count, all due when the window opens.

The window feeds the round to the service just ahead of the packer
(whole requests, at least one wave's rows queued at every wave boundary)
and closes at the first wave retirement at or after ``--seconds``.  A
window holds a fifth of a round, so the rest is never admitted: the
engine runs the same full waves it would run with the whole round
queued, and every admitted request is still delivered (those after the
close count outside the window).  What grows with a deep queue on the
host is therefore not measured here.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.chip.generator import Request, encodings

clock = time.perf_counter


def requests(traffic: dict, rng, seconds: float, cond_dim: int):
    n = traffic["clients"] * traffic["categories"]
    enc = encodings(rng, n, cond_dim)
    return [Request(0.0, int(traffic["images"]), int(traffic["steps"]),
                    float(traffic["guidance"]), enc[i],
                    i % traffic["categories"]) for i in range(n)]


def submit(svc, req):
    return svc.submit(req.cond, req.category, req.images,
                      guidance=req.guidance, num_steps=req.steps)


def warm_up(system, svc, traffic: dict, reqs, key, cond_dim: int):
    """A backlog drains in full waves of one shape: one real wave of
    warm-up encodings compiles (or loads) and runs it."""
    k = traffic["images"]
    rng = np.random.default_rng(12345)
    for e in encodings(rng, system.wave_size // k, cond_dim):
        svc.submit(e, 0, k, guidance=traffic["guidance"],
                   num_steps=traffic["steps"])
    svc.drain(key)


def window(svc, system, reqs, seconds: float, key, on_close=None) -> dict:
    """Serve the window; returns its record."""
    W = system.wave_size
    eng = svc.engine
    gen0 = eng.stats["generated"]
    base = dict(eng.stats)
    pending = iter(reqs)
    sub, futs = {}, {}            # rid -> Request, rid -> future
    st = {"rows": 0, "closed": False, "t_end": None, "exhausted": False}
    t0 = clock()

    def poll():
        with jax.profiler.TraceAnnotation("bench.poll"):
            if st["closed"]:
                return False
            last = max(svc.delivered.values(), default=None)
            if last is not None and last >= t0 + seconds:
                st["closed"], st["t_end"] = True, last
                st["stats"] = dict(eng.stats)
                if on_close is not None:
                    on_close()
                return False
            queued = st["rows"] - (eng.stats["generated"] - gen0)
            with jax.profiler.TraceAnnotation("bench.submit"):
                while queued < W:
                    req = next(pending, None)
                    if req is None:
                        st["exhausted"] = True
                        break
                    fut = submit(svc, req)
                    sub[fut.rid], futs[fut.rid] = req, fut
                    st["rows"] += req.images
                    queued += req.images
            return not st["exhausted"] or queued > 0

    with jax.profiler.TraceAnnotation("bench.drain"):
        svc.drain(key, poll=poll)
    if not st["closed"]:          # the round ran out before the window did
        st["t_end"] = max(svc.delivered.values())
        st["stats"] = dict(eng.stats)
        if on_close is not None:
            on_close()
    t_end = st["t_end"]
    done = {rid: t for rid, t in svc.delivered.items() if rid in sub}
    in_window = [rid for rid, t in done.items() if t <= t_end]
    end = st["stats"]
    delta = {k: end[k] - base[k] for k in end if isinstance(end[k], int)}
    retired = sorted({round(t - t0, 3) for t in done.values()})
    return {"kind": "backlog", "t0": t0, "t_end": t_end,
            "window_s": t_end - t0, "submitted": sub, "futures": futs,
            "delivered": done, "in_window": in_window,
            "images": sum(sub[r].images for r in in_window),
            "counters": delta, "wave_rows": W,
            "note": f"deliveries at (s after the window opened) {retired}"}
