"""Observability layer: span tracing, metrics registry, Chrome trace
export — and the gate that tracing never changes a single D_syn bit."""
import json

import jax
import numpy as np
import pytest

from repro.configs.oscar import DiffusionConfig
from repro.diffusion.dit import init_dit
from repro.diffusion.schedule import make_schedule
from repro.obs import (LIFECYCLE_STAGES, FakeClock, Histogram,
                       MetricsRegistry, NULL_SPAN, Tracer, chrome_trace,
                       validate_chrome_trace, write_trace)
from repro.serve.service import SynthesisService
from repro.serve.synthesis import SynthesisEngine

DC = DiffusionConfig(d_model=32, num_layers=1, num_heads=2,
                     sample_timesteps=3, train_timesteps=16)
H = 8


@pytest.fixture(scope="module")
def dm():
    key = jax.random.PRNGKey(0)
    params = init_dit(key, DC, H, 3)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])
    sched = make_schedule(DC.train_timesteps, DC.schedule)
    return params, sched


def _engine(dm, **kw):
    params, sched = dm
    kw.setdefault("image_size", H)
    kw.setdefault("wave_size", 8)
    return SynthesisEngine(params, DC, sched, **kw)


def _enc(seed):
    e = np.random.default_rng(seed).normal(size=(DC.cond_dim,))
    return (e / np.linalg.norm(e)).astype(np.float32)


# ---------------------------------------------------------------- tracer --

def test_span_nesting_attrs_and_fake_clock():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", host=1):
        clk.advance(1.0)
        with tr.span("inner", wave=3) as sp:
            clk.advance(0.25)
            sp.set(rows=64)
        clk.advance(0.5)
    # spans record on exit: inner closes first
    inner, outer = tr.spans
    assert inner.name == "inner" and inner.depth == 1
    assert inner.start == 1.0 and inner.duration == 0.25
    assert inner.attrs == {"wave": 3, "rows": 64}
    assert outer.name == "outer" and outer.depth == 0
    assert outer.start == 0.0 and outer.duration == 1.75
    assert outer.attrs == {"host": 1} and outer.end == 1.75


def test_span_records_on_exception():
    tr = Tracer(clock=FakeClock(tick=1.0))
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("boom")
    assert [s.name for s in tr.spans] == ["doomed"]
    assert not tr._stack                       # stack unwound cleanly


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    assert tr.span("x", a=1) is NULL_SPAN      # one shared object, no alloc
    assert tr.span("y") is NULL_SPAN
    with tr.span("z") as sp:
        sp.set(ignored=True)
    tr.instant("m")
    tr.stamp(7, "admit")
    assert tr.spans == [] and tr.lifecycle == {}


def test_lifecycle_stamps_first_wins_and_latency():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    for t, stage in enumerate(LIFECYCLE_STAGES):
        clk.advance(1.0)
        tr.stamp(0, stage)
    tr.stamp(0, "pack")                        # second pack is ignored
    assert tr.lifecycle[0]["pack"] == 3.0
    lat = tr.request_latency(0)
    assert lat["queue_wait"] == 2.0            # enqueue@2 → dispatch@4
    assert lat["e2e_latency"] == 5.0           # admit@1 → deliver@6
    assert tr.request_latency(99) == {}
    with pytest.raises(ValueError):
        tr.stamp(0, "not-a-stage")


# --------------------------------------------------------------- metrics --

def test_histogram_quantiles_vs_numpy_oracle():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-4.0, sigma=1.5, size=5000)
    h = Histogram()
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    assert h.min == vals.min() and h.max == vals.max()
    np.testing.assert_allclose(h.sum, vals.sum(), rtol=1e-9)
    for q in (0.5, 0.9, 0.99):
        oracle = np.quantile(vals, q)
        # geometric buckets at 8/decade: estimate within ~33 % relative
        assert abs(h.quantile(q) - oracle) / oracle < 0.35, (q, oracle)
    p = h.percentiles()
    assert p["p50"] <= p["p90"] <= p["p99"] <= h.max


def test_histogram_edge_cases():
    h = Histogram(buckets=(1.0, 2.0, 4.0))
    assert np.isnan(h.quantile(0.5))           # empty
    h.observe(0.5)                             # underflow bucket
    h.observe(100.0)                           # overflow bucket
    assert h.quantile(0.0) >= h.min and h.quantile(1.0) <= h.max
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram(buckets=(2.0, 1.0))          # non-increasing edges


def test_registry_labels_drop_and_dump():
    m = MetricsRegistry()
    m.inc("host.rows", 5, host=0)
    m.inc("host.rows", 7, host=1)
    m.set_gauge("hosts", 2)
    m.observe("lat", 0.5)
    assert m.get("host.rows", host=0) == 5
    assert m.get("host.rows", host=1) == 7
    assert m.get("absent") == 0 and m.get("absent", default=None) is None
    d = m.as_dict()
    assert d["host.rows{host=0}"] == 5 and d["hosts"] == 2
    assert d["lat"]["count"] == 1 and d["lat"]["p50"] == 0.5
    m.drop("host.")
    assert m.get("host.rows", host=0) == 0
    assert m.get("hosts") == 2                 # prefix match, not substring
    with pytest.raises(TypeError):
        m.inc("hosts")                         # gauge used as counter


# ---------------------------------------------------------------- export --

def _traced_drain(dm, **kw):
    tr = Tracer()
    eng = _engine(dm, tracer=tr, **kw)
    rids = [eng.submit(_enc(i), i % 3, c) for i, c in enumerate((3, 5, 2, 6))]
    out = eng.run(jax.random.PRNGKey(1))
    return tr, eng, [out[r] for r in rids]


def test_chrome_trace_export_and_validation(dm, tmp_path):
    tr, eng, _ = _traced_drain(dm, hosts=2)
    path = tmp_path / "trace.json"
    obj = write_trace(path, tr, registry=eng.metrics, hosts=2)
    assert validate_chrome_trace(obj, require_hosts=2) > 0
    on_disk = json.loads(path.read_text())
    tracks = {e["args"]["name"] for e in on_disk["traceEvents"]
              if e.get("name") == "thread_name"}
    assert {"scheduler", "host 0", "host 1"} <= tracks
    spans = [e for e in on_disk["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    # per-window spans carry the host onto that host's track
    host_tids = {e["tid"] for e in spans if e["name"] == "window.pack"}
    assert len(host_tids) == 2
    assert on_disk["metrics"]["requests"] == 4


def test_validate_rejects_malformed_traces():
    with pytest.raises(ValueError, match="no traceEvents"):
        validate_chrome_trace({})
    no_spans = {"traceEvents": [{"ph": "M", "ts": 0, "pid": 0, "tid": 0,
                                 "name": "process_name", "args": {}}]}
    with pytest.raises(ValueError, match="no complete"):
        validate_chrome_trace(no_spans)
    bad = {"traceEvents": [{"ph": "X", "ts": 1, "pid": 0, "tid": 0,
                            "name": "s", "dur": -5}]}
    with pytest.raises(ValueError, match="negative"):
        validate_chrome_trace(bad)
    missing = {"traceEvents": [{"ph": "X", "ts": 1, "dur": 1, "name": "s"}]}
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace(missing)
    ok = {"traceEvents": [{"ph": "X", "ts": 1, "pid": 0, "tid": 0,
                           "name": "s", "dur": 1}]}
    with pytest.raises(ValueError, match="missing host tracks"):
        validate_chrome_trace(ok, require_hosts=1)


# ----------------------------------------------------- engine integration --

MODES = [dict(), dict(ragged=True), dict(compaction="full"),
         dict(hosts=2), dict(compaction="full", hosts=2)]


@pytest.mark.parametrize("kw", MODES,
                         ids=["grouped", "ragged", "compacted", "placed",
                              "placed_compacted"])
def test_dsyn_bit_identical_tracing_on_vs_off(dm, kw):
    """The determinism gate: spans and stamps observe the drain — they
    must never key noise, schedule waves, or order anything."""
    _, eng_off, out_off = (None, *(_traced_drain(dm, **kw)[1:]))
    eng_off2 = _engine(dm, **kw)               # untraced control
    rids = [eng_off2.submit(_enc(i), i % 3, c)
            for i, c in enumerate((3, 5, 2, 6))]
    out_plain = eng_off2.run(jax.random.PRNGKey(1))
    for traced, plain in zip(out_off, (out_plain[r] for r in rids)):
        assert np.array_equal(traced, plain)
    assert eng_off.stats == eng_off2.stats


@pytest.mark.parametrize("kw", MODES[:4],
                         ids=["grouped", "ragged", "compacted", "placed"])
def test_stats_view_backward_compatible(dm, kw):
    """The legacy ``stats`` dict view must keep every pre-registry key
    (including the per-host breakdown) with identical values."""
    eng = _engine(dm, **kw)
    for i, c in enumerate((3, 5, 2, 6)):
        eng.submit(_enc(i), i % 3, c)
    eng.run(jax.random.PRNGKey(1))
    s = eng.stats
    for key in ("requests", "waves", "generated", "scheduled_rows",
                "padded", "cache_hits",
                "store_hits", "streamed", "merged_waves", "compiled_shapes",
                "segments", "row_iters_scheduled", "row_iters_active"):
        assert key in s, key
    assert s["requests"] == 4 and s["generated"] == 16
    assert s["scheduled_rows"] == s["generated"] + s["padded"]
    if "hosts" in kw:
        assert s["hosts"] == kw["hosts"]
        assert len(s["per_host"]) == kw["hosts"]
        for p in s["per_host"]:
            assert set(p) == {"rows", "padded", "waves",
                              "row_iters_scheduled", "row_iters_active",
                              "queue_depth_at_start"}
        assert sum(p["rows"] + p["padded"] for p in s["per_host"]) \
            == s["scheduled_rows"]
        assert sum(p["rows"] for p in s["per_host"]) == s["generated"]


def test_engine_lifecycle_stamps_ordered(dm):
    tr, _, _ = _traced_drain(dm)
    for rid, stages in tr.lifecycle.items():
        assert set(stages) == set(LIFECYCLE_STAGES), rid
        times = [stages[st] for st in LIFECYCLE_STAGES]
        assert times == sorted(times), (rid, stages)


def test_service_latency_histograms(dm):
    eng = _engine(dm)
    svc = SynthesisService(eng, key=0, tracer=Tracer())
    futs = [svc.submit(_enc(i), i % 3, 4) for i in range(3)]
    svc.gather(futs)
    e2e = eng.metrics.get("request.e2e_latency", default=None)
    qw = eng.metrics.get("request.queue_wait", default=None)
    assert e2e["count"] == 3 and qw["count"] == 3
    assert e2e["p50"] <= e2e["p99"] and e2e["min"] > 0
    assert all(qw["min"] <= v <= e2e["max"] for v in (qw["p50"], qw["p99"]))
    svc.gather(futs)                           # resolved: no double count
    assert eng.metrics.get("request.e2e_latency", default=None)["count"] == 3
    assert "latency" in svc.stats


# ---------------------------------------------------------------------------
# thread-safety: drain workers hammer one registry / tracer
# ---------------------------------------------------------------------------

def test_metrics_and_tracer_hammer_no_lost_records():
    """N threads × M ops against one MetricsRegistry and one enabled
    Tracer: every increment, span, and stamp lands — the per-host drain
    workers mutate these concurrently, and a torn buffer append or a
    lost counter bump would silently corrupt stats."""
    import threading

    m = MetricsRegistry()
    tr = Tracer(clock=FakeClock(tick=0.001))
    N, M = 8, 300
    start = threading.Barrier(N)

    def worker(tid):
        start.wait()
        for i in range(M):
            m.inc("hits")
            m.inc("host.rows", 2, host=tid)
            m.observe("lat", float(i % 7))
            with tr.span("work", host=tid, i=i):
                tr.stamp(tid * M + i, "admit")
            tr.stamp(tid * M + i, "deliver")

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.get("hits") == N * M
    for tid in range(N):
        assert m.get("host.rows", host=tid) == 2 * M
    assert m.get("lat", default=None)["count"] == N * M
    spans = [s for s in tr.spans if s.name == "work"]
    assert len(spans) == N * M
    assert len(tr.lifecycle) == N * M
    assert all(set(st) == {"admit", "deliver"}
               for st in tr.lifecycle.values())
    # per-thread nesting: every span opened at depth 0 of its own stack
    assert all(s.depth == 0 for s in spans)


def test_disabled_tracer_stays_nullspan_under_threads():
    """The disabled fast path records nothing and allocates nothing:
    every thread gets the one shared NULL_SPAN and no clock is read."""
    import threading

    reads = []
    tr = Tracer(clock=lambda: reads.append(1) or 0.0, enabled=False)

    def worker():
        for i in range(200):
            assert tr.span("x", i=i) is NULL_SPAN
            tr.stamp(i, "admit")

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not reads and not tr.spans and not tr.lifecycle


# ---------------------------------------------------------------------------
# the denoiser's named scopes: metadata the device trace reads, never values
# ---------------------------------------------------------------------------

def _op_names(compiled_text: str) -> set[str]:
    import re
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _dit_fn(use_pallas: bool = False):
    from repro.diffusion.dit import dit_apply
    return jax.jit(lambda p, x, t, y: dit_apply(p, DC, x, t, y,
                                                use_pallas=use_pallas))


def test_dit_scopes_in_hlo_metadata_and_bit_identical(dm, monkeypatch):
    """Every sub-block scope is in the compiled program's ``op_name``
    metadata, and the output is bit-identical to the same program traced
    with every scope taken out."""
    import contextlib

    from repro.diffusion.dit import DIT_SCOPES
    params, _ = dm
    x = jax.random.normal(jax.random.PRNGKey(3), (2, H, H, 3))
    t = jax.numpy.array([3, 11])
    y = jax.numpy.asarray(np.stack([_enc(1), _enc(2)]))
    scoped = _dit_fn()
    names = _op_names(scoped.lower(params, x, t, y).compile().as_text())
    parts = {part for n in names for part in n.split("/")}
    assert set(DIT_SCOPES) <= parts
    out = scoped(params, x, t, y)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _dit_fn()
    assert not {p for n in _op_names(plain.lower(params, x, t, y).compile()
                                     .as_text())
                for p in n.split("/")} & set(DIT_SCOPES)
    assert np.array_equal(np.asarray(out), np.asarray(plain(params, x, t, y)))


def test_flash_attention_sits_in_the_attention_core_scope(dm):
    """The fused path's kernel call runs under ``dit.attn.core``, the
    scope the naive score/softmax/PV chain runs under."""
    import re
    params, _ = dm
    x = jax.numpy.zeros((2, H, H, 3))
    t = jax.numpy.array([1, 2])
    y = jax.numpy.asarray(np.stack([_enc(1), _enc(2)]))
    text = _dit_fn(use_pallas=True).lower(params, x, t, y).as_text(
        debug_info=True)
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    assert any(n.endswith("dit.attn.core/jit(flash_attention_bhsd)")
               for n in locs)


def test_wave_program_scan_body_scoped(dm):
    """The ragged wave program's scan body runs under ``sampler.step``,
    the denoiser's ops inside it under their own scopes."""
    from repro.diffusion.sampler import _ragged_core
    from repro.diffusion.guidance import ragged_tables
    params, sched = dm
    steps = np.array([3, 2], np.int32)
    ts, ab_t, ab_prev, jloc = ragged_tables(sched, steps, 3)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    y = jax.numpy.asarray(np.stack([_enc(1), _enc(2)]))
    names = _op_names(_ragged_core.lower(
        params, DC, y, keys, jax.numpy.array([1.0, 2.0]), ts, ab_t, ab_prev,
        jloc, image_size=H, channels=3, eta=1.0, use_pallas=False)
        .compile().as_text())
    assert any("sampler.step/dit.attn.core/" in n for n in names)
    assert any("sampler.step/" in n and "/dit." not in n for n in names)
