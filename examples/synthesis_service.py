"""SynthesisService tour: futures, streaming admission, persistent store,
multi-host topology.

    PYTHONPATH=src python examples/synthesis_service.py

Seconds-scale on CPU (random-init DM — serving cost does not depend on
training).  Four acts:

 1. futures      — submit (client, category) encodings, get
                   SynthesisFutures, drain once, read results;
 2. streaming    — an arrival trace delivers requests mid-drain; the wave
                   packer folds them into the open wave (compare padded
                   rows against draining the same trace as two snapshots);
 3. persistence  — a second service ("cold process") against the same
                   on-disk store serves everything with ZERO sampler
                   calls, bit-identically;
 4. topology     — the same workload drained over 2 SIMULATED HOSTS
                   (``hosts=2``): per-host ingress queues, contiguous
                   per-host wave windows reading one wave-resident scalar
                   table through the segment-offset cfg_fuse path, and a
                   per-host stats breakdown — with D_syn bit-identical to
                   the single-host drain (row noise is keyed by request
                   identity, so placement is invisible).
"""
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.configs.oscar import DiffusionConfig
from repro.diffusion.dit import init_dit
from repro.diffusion.schedule import make_schedule
from repro.serve import SynthesisEngine, SynthesisService, SynthesisStore
from repro.utils import enable_compile_cache

DC = DiffusionConfig(d_model=64, num_layers=2, num_heads=2)
H, STEPS, WAVE = 16, 8, 16


def make_engine():
    key = jax.random.PRNGKey(0)
    params = init_dit(key, DC, H, 3)
    sched = make_schedule(DC.train_timesteps, DC.schedule)
    return SynthesisEngine(params, DC, sched, image_size=H, wave_size=WAVE)


def encodings(n):
    e = np.random.default_rng(0).normal(size=(n, DC.cond_dim))
    return (e / np.linalg.norm(e, axis=-1, keepdims=True)).astype(np.float32)


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    store_dir = Path(tempfile.mkdtemp(prefix="dsyn_store_"))
    enc = encodings(8)

    # -- 1. futures -------------------------------------------------------
    svc = SynthesisService(make_engine(), key=42, store=store_dir)
    futs = [svc.submit(enc[c], c, 6, num_steps=STEPS) for c in range(4)]
    print("submitted:", futs[0], "...")
    imgs = svc.gather(futs)
    print(f"act 1 — futures: {len(imgs)} requests served, "
          f"shapes {imgs[0].shape}, stats {svc.stats}")

    # -- 2. streaming admission ------------------------------------------
    svc2 = SynthesisService(make_engine(), key=42)
    for c in range(4):
        svc2.submit(enc[c], c, 3, num_steps=STEPS)   # 12 rows queued

    trace = [(enc[c], c, 3) for c in range(4, 8)]    # 12 more arrive live

    def poll():
        if not trace:
            return False
        svc2.submit(*trace.pop(0), num_steps=STEPS)
        return True

    svc2.drain(poll=poll)

    # same arrival trace, snapshot-drained: arrivals form a second drain
    snap = SynthesisService(make_engine(), key=42)
    for c in range(4):
        snap.submit(enc[c], c, 3, num_steps=STEPS)
    snap.drain()
    for c in range(4, 8):
        snap.submit(enc[c], c, 3, num_steps=STEPS)
    snap.drain()
    print(f"act 2 — streaming: {svc2.stats['streamed']} requests arrived "
          f"mid-drain and filled open waves — padded rows "
          f"{svc2.stats['padded']} vs {snap.stats['padded']} for snapshot "
          f"drains of the same trace")

    # -- 3. persistent store ---------------------------------------------
    cold = SynthesisService(make_engine(), key=42, store=store_dir)
    futs_cold = [cold.submit(enc[c], c, 6, num_steps=STEPS)
                 for c in range(4)]
    imgs_cold = cold.gather(futs_cold)
    assert cold.stats["generated"] == 0, "warm store should skip sampling"
    assert all(np.array_equal(a, b) for a, b in zip(imgs, imgs_cold))
    print(f"act 3 — store: cold process served {len(imgs_cold)} requests "
          f"from {store_dir.name} with zero sampler calls "
          f"(store_hits={cold.stats['store_hits']}), bit-identical")

    # -- 4. multi-host topology ------------------------------------------
    # one-host oracle (ragged row-keyed waves) vs the same trace placed
    # over two simulated hosts: every request routes to a host ingress
    # queue by identity, each host packs its own wave window, and the
    # output bits cannot tell the difference
    one = SynthesisService(make_engine(), key=7, ragged=True)
    f1 = [one.submit(enc[c], c, 5, num_steps=STEPS) for c in range(6)]
    imgs_one = one.gather(f1)

    duo = SynthesisService(make_engine(), key=7, ragged=True, hosts=2)
    f2 = [duo.submit(enc[c], c, 5, num_steps=STEPS) for c in range(6)]
    imgs_duo = duo.gather(f2)
    assert all(np.array_equal(a, b) for a, b in zip(imgs_one, imgs_duo)), \
        "placement leaked into row values"
    print(f"act 4 — topology: {duo.stats['hosts']} simulated hosts drained "
          f"{duo.stats['generated']} rows, bit-identical to single-host; "
          f"per-host stats:")
    for h, p in enumerate(duo.stats["per_host"]):
        print(f"          host {h}: rows={p['rows']} padded={p['padded']} "
              f"waves={p['waves']} iters={p['row_iters_scheduled']}"
              f"/{p['row_iters_active']} "
              f"queue_depth_at_start={p['queue_depth_at_start']}")


if __name__ == "__main__":
    main()
