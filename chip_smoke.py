"""Smoke run of the OSCAR round on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the placed drain over four chips

One chip runs three phases at the "paper" preset's full widths (DiT
d_model 144, 4 layers, 4 heads, patch 4, 16×16×3 pixels, 512-d
conditioning; 10 categories × 6 domains; ResNet-18; T = 50 sampling
steps), with only the step counts of training cut:

1. trainer: ``Experiment`` pre-trains the DM for a few steps at batch 128;
2. server: a full OSCAR round (``run_oscar``): six clients upload their
   C × 512 encodings, a ``SynthesisService`` drains them in merged ragged
   waves (10 samples per category, 600 images), the global classifier
   trains a few steps and is evaluated.  A handful of rows is checked
   against the same drain run on the host CPU at float32 matmul
   precision;
3. fused: the same round with ``use_pallas=True``; its wave program must
   hold the cfg_fuse, flash_attention and adaln_norm kernels as
   ``tpu_custom_call`` ops, and its rows must match phase 2's.

``--chips 4`` runs only the server phase's drain, once over a four-host
serving mesh (one chip per host) and once over a one-host mesh on device
0, and compares the two.

Everything is generated from ``--seed``.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, outside a checkout of the repo, or when any check fails,
the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

PRETRAIN_STEPS = 20
CLASSIFIER_STEPS = 20
SAMPLES_PER_CATEGORY = 10
REF_REQUESTS = 2          # requests (of 10 rows each) checked against the CPU
# Tolerances against the float32 CPU reference (matmuls at "highest").
# XLA's default precision for a float32 matmul on the TPU is one bf16
# pass: 8-bit mantissa inputs, f32 accumulation, about 2^-9 relative
# error per product.
# One denoiser call on a probe batch: max |Δε| relative to max |ε|.
# Four blocks of bf16-rounded matmuls stay well under 1e-2; a wrong
# kernel, layout or mask is off by O(1).
TOL_CALL = 2e-2
# A 50-step guided trajectory amplifies any per-step difference: on a
# v5e at "highest" precision, where only the transcendentals and the
# summation order differ from the CPU, 20 rows already differ by up to
# 8e-3 per pixel.  So rows are held to bounds relative to rows drawn
# with another noise key (about 0.46 apart per pixel): mean |Δ| below a
# tenth of that, max |Δ| below a quarter of the [-1, 1] range.
TOL_ROWS_FRAC, TOL_ROWS_MAX = 0.1, 0.5
# The same trajectory with the chip at "highest" precision too.
TOL_HIGHEST_MEAN, TOL_HIGHEST_MAX = 2e-3, 5e-2


def tpu_devices(count: int):
    """The TPU devices, or exit non-zero before any work."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: --chips {count} needs {count} TPU "
                         f"devices, JAX found {len(devs)}")
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: {ROOT} is not a checkout of the repo "
                         f"(no src/repro beside this script)")
    sys.path.insert(0, str(ROOT / "src"))
    return devs


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def smoke_config(seed: int):
    from repro.configs.oscar import paper_preset
    ocfg = paper_preset()
    return replace(ocfg, seed=seed,
                   data=replace(ocfg.data, seed=seed),
                   diffusion=replace(ocfg.diffusion,
                                     pretrain_steps=PRETRAIN_STEPS),
                   classifier_steps=CLASSIFIER_STEPS,
                   samples_per_category=SAMPLES_PER_CATEGORY)


def perturb(params, seed: int, scale: float = 0.05):
    """Move every parameter off its adaLN-zero init, as the parity tests
    do: after a few pretrain steps the modulation gates and the output
    head are still near zero, which would leave the transformer blocks,
    and so the kernels, with almost no say in the rows compared."""
    import jax
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        a + scale * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


def probe_eps(params, ocfg, enc, seed: int, *, device, precision: str,
              use_pallas: bool = False) -> np.ndarray:
    """ε_θ of one denoiser call on ``device`` at matmul ``precision``,
    for a probe batch of 8 encodings with noise and timesteps drawn on
    the host, so every device sees the same inputs."""
    import jax
    from repro.diffusion.dit import dit_apply
    dc, s = ocfg.diffusion, ocfg.data.image_size
    rng = np.random.default_rng(seed)
    y = np.asarray(enc.reshape(-1, enc.shape[-1])[:8], np.float32)
    x = rng.standard_normal((len(y), s, s, ocfg.data.channels), np.float32)
    t = rng.integers(0, dc.train_timesteps, len(y)).astype(np.int32)
    fn = jax.jit(lambda p, x, t, y: dit_apply(p, dc, x, t, y,
                                              use_pallas=use_pallas))
    with jax.default_device(device), jax.default_matmul_precision(precision):
        return np.asarray(fn(jax.device_put(params, device), x, t, y))


def call_diff(eps, ref) -> float:
    """max |Δε| relative to max |ε_ref| of one probe call."""
    return float(np.abs(eps - ref).max() / np.abs(ref).max())


def row_diff(a, b) -> dict:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return {"max_abs": float(d.max()), "mean_abs": float(d.mean()),
            "bit_identical": bool(np.array_equal(a, b))}


def check_rows(x, n: int, what: str):
    check(x.shape[0] == n, f"{what}: {x.shape[0]} rows, expected {n}")
    check(bool(np.isfinite(x).all()), f"{what}: non-finite rows")
    check(float(x.min()) >= -1.0 and float(x.max()) <= 1.0,
          f"{what}: rows outside [-1, 1]")


def phase_trainer(ocfg, cache_dir: str):
    from repro.core.experiment import Experiment
    t0 = time.perf_counter()
    exp = Experiment(ocfg, cache_dir=cache_dir,
                     pretrain_steps=ocfg.diffusion.pretrain_steps)
    losses = [loss for _, loss in exp.dm_losses]
    check(len(losses) == ocfg.diffusion.pretrain_steps,
          f"trainer ran {len(losses)} steps, expected "
          f"{ocfg.diffusion.pretrain_steps} (a cached DM was served?)")
    check(bool(np.isfinite(losses).all()), f"non-finite losses {losses}")
    print(f"trainer: {len(losses)} DM steps at batch "
          f"{ocfg.diffusion.batch_size}, loss {losses[0]} -> {losses[-1]}, "
          f"{time.perf_counter() - t0} s incl. data and compile",
          flush=True)
    return exp


def oscar_round(exp, params, key, store_dir: str, *, use_pallas: bool):
    """One OSCAR round through ``run_oscar`` with its own engine, service
    and store.  Returns (result, engine, seconds)."""
    from repro.core.oscar import run_oscar
    from repro.serve import SynthesisEngine, SynthesisService, SynthesisStore
    ocfg = exp.ocfg
    eng = SynthesisEngine(params, ocfg.diffusion, exp.sched,
                          image_size=ocfg.data.image_size,
                          channels=ocfg.data.channels, use_pallas=use_pallas)
    svc = SynthesisService(eng, store=SynthesisStore(store_dir))
    t0 = time.perf_counter()
    res = run_oscar(key, ocfg, exp.data, params, exp.sched, exp.fm,
                    service=svc, ragged=True,
                    samples_per_category=ocfg.samples_per_category,
                    classifier_steps=ocfg.classifier_steps)
    secs = time.perf_counter() - t0
    R, C = res.encodings.shape[:2]
    n = R * C * ocfg.samples_per_category
    st = eng.stats
    check(st["generated"] == n, f"engine generated {st['generated']} "
          f"rows, expected {n}")
    check(st["store_hits"] == 0 and st["cache_hits"] == 0,
          f"store/cache hits {st['store_hits']}/{st['cache_hits']}")
    check_rows(res.syn_images, n, "D_syn")
    check(all(np.isfinite(v) for v in res.metrics.values()),
          f"non-finite metrics {res.metrics}")
    return res, eng, secs


def reference_rows(exp, params, enc, key, n_req: int, device, precision):
    """The first ``n_req`` requests of the server phase's drain, rerun on
    ``device`` at matmul ``precision``.  Row noise is keyed by request
    identity (rid, row), and a fresh engine numbers requests in the same
    submission order, so these rows are the server's first rows."""
    import jax
    from repro.core.oscar import synthesize
    from repro.diffusion.schedule import make_schedule
    from repro.serve import SynthesisEngine
    ocfg = exp.ocfg
    present = np.zeros(enc.shape[:2], bool)
    present.flat[:n_req] = True
    with jax.default_device(device), jax.default_matmul_precision(precision):
        p = jax.device_put(params, device)
        sched = make_schedule(ocfg.diffusion.train_timesteps,
                              ocfg.diffusion.schedule)
        eng = SynthesisEngine(p, ocfg.diffusion, sched,
                              image_size=ocfg.data.image_size,
                              channels=ocfg.data.channels)
        # run_oscar's synthesis key: split(key, 3) = (enc, syn, clf)
        ksyn = jax.device_put(jax.random.split(key, 3)[1], device)
        x, _ = synthesize(ksyn, p, ocfg.diffusion, sched, enc, present,
                          ocfg.samples_per_category,
                          image_size=ocfg.data.image_size,
                          channels=ocfg.data.channels, engine=eng,
                          ragged=True)
    return x


def phase_server(exp, params, key, store_dir: str, devs, seed: int):
    import jax
    res, eng, secs = oscar_round(exp, params, key, store_dir,
                                 use_pallas=False)
    n = len(res.syn_images)
    print(f"server: {n} rows generated on {devs[0].device_kind} in "
          f"{eng.stats['waves']} waves (no store or cache hits), "
          f"classifier avg acc {res.metrics['avg']}, round {secs} s incl. "
          f"compile", flush=True)
    cpu = jax.devices("cpu")[0]
    eps_ref = probe_eps(params, exp.ocfg, res.encodings, seed, device=cpu,
                        precision="highest")
    scale = float(np.abs(eps_ref).max())
    print(f"perturbed denoiser: max |eps| on a probe batch {scale}",
          flush=True)
    check(scale > 1e-3, "vacuous denoiser")
    dcall = call_diff(probe_eps(params, exp.ocfg, res.encodings, seed,
                                device=devs[0], precision="default"),
                      eps_ref)
    print(f"one denoiser call, chip vs CPU float32 reference: max |d eps| "
          f"/ max |eps| = {dcall}; tolerance < {TOL_CALL}", flush=True)
    check(dcall < TOL_CALL, f"denoiser call vs CPU reference {dcall}")
    k = REF_REQUESTS * exp.ocfg.samples_per_category
    ref = reference_rows(exp, params, res.encodings, key, REF_REQUESTS,
                         cpu, "highest")
    check_rows(ref, k, "CPU reference")
    unrelated = row_diff(ref[:k // 2], ref[k // 2:])["mean_abs"]
    tol_mean = TOL_ROWS_FRAC * unrelated
    d = row_diff(res.syn_images[:k], ref)
    print(f"server vs CPU float32 reference ({k} rows): {d}; rows of "
          f"different requests differ by mean {unrelated}; tolerance mean "
          f"< {tol_mean}, max < {TOL_ROWS_MAX}", flush=True)
    hi = reference_rows(exp, params, res.encodings, key, REF_REQUESTS,
                        devs[0], "highest")
    dh = row_diff(hi, ref)
    print(f"chip at 'highest' precision vs CPU reference: {dh}; tolerance "
          f"mean < {TOL_HIGHEST_MEAN}, max < {TOL_HIGHEST_MAX}", flush=True)
    check(d["mean_abs"] < tol_mean and d["max_abs"] < TOL_ROWS_MAX,
          f"server rows vs CPU reference {d}")
    check(dh["mean_abs"] < TOL_HIGHEST_MEAN
          and dh["max_abs"] < TOL_HIGHEST_MAX, f"chip@highest vs CPU {dh}")
    return res, tol_mean, eps_ref


def wave_kernels(eng) -> dict:
    """Compile each merged-wave geometry the fused engine dispatched and
    name the Pallas kernels in the TPU program."""
    import jax
    import jax.numpy as jnp
    from repro.diffusion.guidance import ragged_tables
    from repro.diffusion.sampler import _ragged_core
    from repro.kernels import compiled_kernels
    dc = eng.dc
    out = {}
    for sig in sorted(eng.traj_shapes):
        check(sig[0] == "cfg-ragged", f"unexpected wave kind {sig}")
        _, B, S = sig
        tables = ragged_tables(eng.sched, np.full(B, S, np.int32), S)
        lowered = _ragged_core.lower(
            eng.dm_params, dc, jnp.zeros((B, dc.cond_dim), jnp.float32),
            jax.random.split(jax.random.PRNGKey(0), B),
            jnp.zeros((B,), jnp.float32), *tables,
            image_size=eng.image_size, channels=eng.channels, eta=eng.eta,
            use_pallas=True)
        out[(B, S)] = compiled_kernels(lowered.compile().as_text())
    return out


def phase_fused(exp, params, key, store_dir: str, plain, tol_mean: float,
                eps_ref, devs, seed: int):
    dcall = call_diff(probe_eps(params, exp.ocfg, plain.encodings, seed,
                                device=devs[0], precision="default",
                                use_pallas=True), eps_ref)
    print(f"one fused denoiser call, chip vs CPU float32 reference: "
          f"max |d eps| / max |eps| = {dcall}; tolerance < {TOL_CALL}",
          flush=True)
    check(dcall < TOL_CALL, f"fused denoiser call vs CPU reference {dcall}")
    res, eng, secs = oscar_round(exp, params, key, store_dir,
                                 use_pallas=True)
    kernels = wave_kernels(eng)
    print(f"fused: {len(res.syn_images)} rows, round {secs} s incl. "
          f"compile; tpu_custom_call kernels per wave (rows, steps): "
          f"{ {k: sorted(v) for k, v in kernels.items()} }", flush=True)
    for geom, names in kernels.items():
        for fam in ("cfg_fuse", "flash_attention", "adaln_norm"):
            check(any(n.startswith(fam) for n in names),
                  f"wave {geom}: no compiled {fam} kernel in {names}")
    d = row_diff(res.syn_images, plain.syn_images)
    print(f"fused vs plain server rows ({len(res.syn_images)}): {d}; "
          f"tolerance mean < {tol_mean}, max < {TOL_ROWS_MAX}", flush=True)
    check(d["mean_abs"] < tol_mean and d["max_abs"] < TOL_ROWS_MAX,
          f"fused rows vs plain rows {d}")


class _PlacementProbe:
    """Mixin that records, per host, the devices of every window output
    and the identity of the parameters each segment dispatch used."""

    def _fence_window(self, w, x, wave):
        self.window_devices.setdefault(w.host, set()).update(x.devices())
        return super()._fence_window(w, x, wave)

    def _window_params(self, host):
        p = super()._window_params(host)
        self.param_ids.setdefault(host, set()).add(id(p))
        return p


def placed_drain(ocfg, params, sched, enc, key, mesh):
    """The server phase's drain over a serving mesh's host topology.
    Returns (rows, engine)."""
    from repro.core.oscar import synthesize
    from repro.serve import HostTopology, SynthesisEngine

    class Probe(_PlacementProbe, SynthesisEngine):
        pass

    eng = Probe(params, ocfg.diffusion, sched,
                image_size=ocfg.data.image_size,
                channels=ocfg.data.channels,
                topology=HostTopology.from_mesh(mesh))
    eng.window_devices, eng.param_ids = {}, {}
    x, _ = synthesize(key, params, ocfg.diffusion, sched, enc,
                      np.ones(enc.shape[:2], bool),
                      ocfg.samples_per_category,
                      image_size=ocfg.data.image_size,
                      channels=ocfg.data.channels, engine=eng, ragged=True)
    return x, eng


def check_placement(eng, what: str) -> list:
    """Each host's windows ran on its own submesh, with parameters placed
    there once.  Returns per-host (rows, devices) for the record."""
    import jax
    topo = eng.topology
    rows = []
    for h in range(topo.num_hosts):
        sub = set(topo.host_mesh(h).devices.flat)
        got = eng.window_devices.get(h, set())
        check(got == sub, f"{what}: host {h} windows on {got}, not {sub}")
        n = eng.stats["per_host"][h]["rows"]
        check(n > 0, f"{what}: host {h} served no rows")
        check(len(eng.param_ids.get(h, ())) == 1,
              f"{what}: host {h} used {len(eng.param_ids.get(h, ()))} "
              f"parameter placements")
        for leaf in jax.tree.leaves(eng._host_params[h]):
            check(leaf.devices() == sub,
                  f"{what}: host {h} parameters on {leaf.devices()}")
        rows.append((n, sorted(d.id for d in sub)))
    return rows


def phase_four_chips(ocfg, seed: int, devs):
    import jax
    from repro.core.oscar import client_encodings
    from repro.data.federated import make_federated_data
    from repro.diffusion.dit import init_dit
    from repro.diffusion.schedule import make_schedule
    from repro.encoders.foundation import FrozenFM
    from repro.launch.mesh import make_serving_mesh
    dc = ocfg.diffusion
    data = make_federated_data(ocfg.data)
    enc, _ = client_encodings(FrozenFM(ocfg.encoding_dim), data)
    params = perturb(init_dit(jax.random.PRNGKey(seed), dc,
                              ocfg.data.image_size, ocfg.data.channels),
                     seed + 1)
    eps = probe_eps(params, ocfg, enc, seed, device=devs[0],
                    precision="default")
    check(float(np.abs(eps).max()) > 1e-3, "vacuous denoiser")
    sched = make_schedule(dc.train_timesteps, dc.schedule)
    key = jax.random.PRNGKey(seed + 2)
    mesh4 = make_serving_mesh(hosts=4)
    mesh1 = make_serving_mesh(hosts=1)
    check(mesh1.devices.flat[0] == devs[0], "one-host mesh is not device 0")
    t0 = time.perf_counter()
    x4, e4 = placed_drain(ocfg, params, sched, enc, key, mesh4)
    t4 = time.perf_counter() - t0
    t0 = time.perf_counter()
    x1, e1 = placed_drain(ocfg, params, sched, enc, key, mesh1)
    t1 = time.perf_counter() - t0
    n = enc.shape[0] * enc.shape[1] * ocfg.samples_per_category
    check_rows(x4, n, "four-host drain")
    check_rows(x1, n, "one-host drain")
    p4 = check_placement(e4, "four hosts")
    p1 = check_placement(e1, "one host")
    print(f"four hosts: per-host (rows, device ids) {p4}; "
          f"{e4.stats['waves']} waves; {t4} s incl. compile", flush=True)
    print(f"one host: per-host (rows, device ids) {p1}; "
          f"{e1.stats['waves']} waves; {t1} s incl. compile", flush=True)
    print(f"four hosts vs one host ({n} rows): {row_diff(x4, x1)}",
          flush=True)


def run_one_chip(ocfg, seed: int, devs, tmp: str):
    import jax
    exp = phase_trainer(ocfg, str(Path(tmp) / "dm_cache"))
    params = perturb(exp.dm_params, seed + 1)
    key = jax.random.PRNGKey(seed + 2)
    plain, tol_mean, eps_ref = phase_server(
        exp, params, key, str(Path(tmp) / "plain"), devs, seed)
    phase_fused(exp, params, key, str(Path(tmp) / "fused"), plain, tol_mean,
                eps_ref, devs, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devs = tpu_devices(args.chips)
    from repro.utils import enable_compile_cache
    print(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{enable_compile_cache(ROOT)}", flush=True)
    ocfg = smoke_config(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(ocfg, args.seed, devs)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            run_one_chip(ocfg, args.seed, devs, tmp)
    print(f"all phases passed in {time.perf_counter() - t0} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
