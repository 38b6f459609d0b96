"""The program's own names in a profiler trace: an enabled tracer's spans
reach it as ``synth.*`` and change no row; ``tracescopes`` attributes
device time to the denoiser's scopes and idle time to the innermost span,
on hand-made traces and on one recorded on a TPU v5e
(``data/trace_b2_round_drain_scoped.json``: the 180 ms of a traced
``b2.round_drain`` window around its first wave boundary, with an
enabled tracer, as ``tracescopes.load`` reads it, the stretch's ends
made its ``bench.window``)."""
from __future__ import annotations

import json
import tempfile
from pathlib import Path

import numpy as np
import pytest

from chipbench_util import ROOT  # noqa: F401 - puts the repo on sys.path

from benchmarks.chip import tracereduce, tracescopes

DATA = Path(__file__).resolve().parent / "data"
SCOPED = DATA / "trace_b2_round_drain_scoped.json"
# whole 18 s and 54 s windows read 45.718-45.725 % on a TPU v5e; the
# stretch holds about one scan step's layers, split over two waves
RECORDED_ATTENTION_CORE_SHARE = (45.0, 46.5)


# -- the engine's spans in a jax.profiler trace (CPU) -------------------------

def _drain(tracer, **kw):
    import jax

    from repro.configs.oscar import DiffusionConfig
    from repro.diffusion.dit import init_dit
    from repro.diffusion.schedule import make_schedule
    from repro.serve.synthesis import SynthesisEngine
    dc = DiffusionConfig(d_model=32, num_layers=1, num_heads=2,
                         sample_timesteps=3, train_timesteps=16)
    eng = SynthesisEngine(init_dit(jax.random.PRNGKey(0), dc, 8, 3), dc,
                          make_schedule(16, dc.schedule), image_size=8,
                          wave_size=8, ragged=True, tracer=tracer, **kw)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.normal(size=dc.cond_dim).astype(np.float32),
                       i % 3, c) for i, c in enumerate((3, 5, 2, 6))]
    out = eng.run(jax.random.PRNGKey(1))
    return [out[r] for r in rids]


@pytest.mark.parametrize("kw,names", [
    ({}, ("wave.admit", "wave.pack", "wave.dispatch", "device.scan",
          "wave.retire")),
    ({"hosts": 2}, ("wave.admit", "window.pack", "window.dispatch",
                    "device.scan", "wave.retire"))], ids=["ragged", "placed"])
def test_tracer_spans_reach_the_profiler_trace(kw, names):
    import jax

    from repro.obs import Tracer
    plain = _drain(Tracer(enabled=False), **kw)
    with tempfile.TemporaryDirectory() as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            traced = _drain(Tracer(), **kw)
        finally:
            jax.profiler.stop_trace()
        doc = tracescopes.load(tracereduce.find_xplane(tdir))
    for a, b in zip(plain, traced):
        assert np.array_equal(a, b)
    spans = doc["host_spans"]
    for name in names:
        got = [args for n, _, _, args, _ in spans if n == "synth." + name]
        assert got, name
        assert all(isinstance(a.get("wave"), int) for a in got), name
    dispatched = {a["wave"] for n, _, _, a, _ in spans
                  if n in ("synth.wave.dispatch", "synth.window.dispatch")}
    retired = {a["wave"] for n, _, _, a, _ in spans
               if n == "synth.wave.retire"}
    assert dispatched == retired == set(range(len(dispatched)))
    assert any(n == "synth.drain" for n, *_ in spans)


# -- a hand-made trace --------------------------------------------------------

TEXTS = [
    "%fusion.1 = f32[240,12,257]{2,1,0} fusion(f32[240,12,257,257] %b), "
    "kind=kLoop, calls=%fused_computation.60",
    "%convolution_add_fusion.3 = f32[240,4608]{1,0} fusion(bf16[240,768] "
    "%c, bf16[768,4608] %w), kind=kOutput, calls=%fused_computation.493",
    "%copy.4 = f32[120,32,32,4]{0,2,3,1} copy(f32[120,32,32,4] %x)",
    "%while.3 = (s32[], f32[120,32,32,4]) while((s32[], f32[120,32,32,4]) "
    "%tuple), condition=%cond, body=%body",
]
SCOPES = ["dit.attn.core", "dit.mlp", None, None]


def _doc():
    # one chip, ns.  Busy [10, 60) [70, 90) [95, 100); gaps [0, 10)
    # [60, 70) [90, 95)
    return {"ops": TEXTS, "scopes": SCOPES, "devices": {"/device:TPU:0": [
        [0, 10, 30],      # [10, 40) attention
        [1, 30, 30],      # [30, 60) mlp
        [1, 70, 20],      # [70, 90) mlp
        [2, 95, 20],      # [95, 115) clipped: unscoped
        [3, 72, 10]]},    # control flow: no scope's time
        "spans": [["bench.window", 0, 100], ["bench.drain", 0, 100],
                  ["bench.poll", 62, 2]],
        "host_spans": [
            ["bench.window", 0, 100, {}, 1], ["bench.drain", 0, 100, {}, 1],
            ["synth.drain", 0, 100, {}, 1],
            # wave 1's admission [58, 66) holds the load generator's poll
            ["synth.wave.admit", 58, 8, {"wave": 1}, 1],
            ["bench.poll", 62, 2, {}, 1],
            ["synth.wave.pack", 66, 3, {"wave": 1}, 1],
            ["synth.wave.dispatch", 69, 2, {"wave": 1}, 1],
            # wave 0's retire after its fence; a compile inside it
            ["synth.device.scan", 71, 19, {"wave": 0}, 1],
            ["synth.wave.retire", 90, 6, {"wave": 0}, 1],
            ["compile", 91, 2, {}, 1],
            ["synth.wave.admit", 4, 2, {"wave": 0}, 1],
            ["synth.wave.pack", 6, 1, {"wave": 0}, 1],
            ["synth.wave.dispatch", 7, 2, {"wave": 0}, 1]]}


def test_scope_of_takes_the_innermost_program_scope():
    assert tracescopes.scope_of(
        "jit(_ragged_core)/while/body/sampler.step/dit.attn.core/exp") \
        == "dit.attn.core"
    assert tracescopes.scope_of(
        "jit(_ragged_core)/while/body/sampler.step/add") == "sampler.step"
    assert tracescopes.scope_of("jit(_wave_row_keys)/threefry2x32") is None
    assert tracescopes.scope_of(None) is None


def test_op_xla_inserted_takes_its_readers_scope():
    """A relayout copy and a prefetch chain carry no scope of their own;
    each takes the scope of the first op of its program that reads it.
    A fused computation named in ``calls=`` is no operand, and an op of
    another program is no reader."""
    texts = [
        "%copy.667 = bf16[240,257,1,12,64]{1,4,3,0,2} copy(bf16[240,257,1,"
        "12,64]{0,4,3,2,1} %get-tuple-element.2474)",
        "%slice-start.1 = ((bf16[768,3072]), bf16[192,3072], s32[]) "
        "slice-start(bf16[768,3072] %get-tuple-element.9)",
        "%slice-done.1 = bf16[192,3072] slice-done(((bf16[768,3072]), "
        "bf16[192,3072], s32[]) %slice-start.1)",
        "%custom-call.42 = bf16[768,3072] custom-call(bf16[192,3072] "
        "%slice-done.1), custom_call_target=\"ConcatBitcast\"",
        "%fusion.9 = f32[240,12,257,257] fusion(bf16[240,257,1,12,64] "
        "%copy.667), kind=kOutput, calls=%fused_computation.3",
        "%fusion.10 = f32[240,257,3072] fusion(bf16[768,3072] "
        "%custom-call.42), kind=kOutput, calls=%fused_computation.4",
        "%fused_computation.3 = f32[2] copy(f32[2] %x)",
        "%copy.2 = f32[2] copy(f32[2] %y)",
        "%fusion.11 = f32[2] fusion(f32[2] %copy.2), kind=kLoop",
    ]
    scopes = [None, None, None, None, "dit.attn.core", "dit.mlp", None,
              None, "dit.mlp"]
    programs = ["jit(a)"] * 6 + ["jit(a)", "jit(b)", "jit(a)"]
    assert tracescopes._inherit_scopes(texts, scopes, programs) == [
        "dit.attn.core", "dit.mlp", "dit.mlp", "dit.mlp", "dit.attn.core",
        "dit.mlp", None, None, "dit.mlp"]


def test_hand_made_scopes_and_spans():
    doc = _doc()
    red = tracescopes.reduce(doc)
    base = tracereduce.reduce(doc)
    assert {k: red[k] for k in base} == base
    assert red["by_scope_s"] == pytest.approx(
        {"dit.attn.core": 30e-9, "dit.mlp": 50e-9, "unscoped": 5e-9})
    # idle: [0, 10) synth.wave.admit [4, 6), pack [6, 7), dispatch [7, 9),
    # the rest under no span; [60, 70) admit 2 + poll 2 + admit 2 + pack 3
    # + dispatch 1; [90, 95) retire 1 + compile 2 + retire 2.  The outer
    # bench.window, bench.drain and synth.drain label nothing.
    assert red["idle_by_span_s"] == pytest.approx({
        "engine host work": 5e-9, "synth.wave.admit": 6e-9,
        "synth.wave.pack": 4e-9, "synth.wave.dispatch": 3e-9,
        "bench.poll": 2e-9, "synth.wave.retire": 3e-9, "compile": 2e-9})
    assert [label for label, _ in red["top_gaps_by_span"]] == [
        "engine host work", "synth.wave.admit", "synth.wave.retire"]
    # wave 0's four spans by self time: its retire less the compile in
    # it.  Wave 1's retire is not in the trace, so wave 1 is left out.
    assert red["wave_host_s"] == pytest.approx({0: (2 + 1 + 2 + 4) / 1e9})
    assert red["spans_in_window"]["synth.wave.admit"] == [
        2, pytest.approx(10e-9)]
    assert tracescopes.scoped_share(red) == pytest.approx(80 / 85)
    assert tracescopes.named_idle_share(red) == pytest.approx(20 / 25)


def test_doc_without_scopes_or_host_spans_reduces():
    doc = _doc()
    del doc["scopes"], doc["host_spans"]
    red = tracescopes.reduce(doc)
    assert red["by_scope_s"] == pytest.approx({"unscoped": 85e-9})
    assert red["idle_by_span_s"] == pytest.approx({"engine host work":
                                                   25e-9})
    assert red["wave_host_s"] == {}
    assert tracescopes.reduce({"devices": {}, "spans": []}) is None


# -- the two readings ---------------------------------------------------------

def test_readings_on_a_hand_made_reduction():
    red = {"busy_s": 2.0, "chips": 1,
           "by_scope_s": {"dit.attn.core": 0.9, "dit.mlp": 1.0,
                          "unscoped": 0.1},
           "wave_host_s": {3: 0.002, 4: 0.004}}
    assert tracescopes.attention_core_share(red) == pytest.approx(45.0)
    assert tracescopes.wave_host_ms(red) == pytest.approx(3.0)
    # a program without the scopes or the spans reads nothing
    assert tracescopes.attention_core_share(
        {**red, "by_scope_s": {"unscoped": 2.0}}) is None
    assert tracescopes.wave_host_ms({**red, "wave_host_s": {}}) is None
    assert tracescopes.attention_core_share(None) is None
    assert tracescopes.wave_host_ms(None) is None


# -- recorded on the chip -----------------------------------------------------

def test_existing_keys_unchanged_on_the_recorded_trace():
    doc = json.loads((DATA / "trace_b2_round_drain.json").read_text())
    base = tracereduce.reduce(doc)
    red = tracescopes.reduce(doc)
    assert {k: red[k] for k in base} == base
    assert tracescopes.attention_core_share(red) is None


def test_recorded_scoped_trace():
    doc = json.loads(SCOPED.read_text())
    red = tracescopes.reduce(doc)
    assert tracescopes.scoped_share(red) >= 0.95
    lo, hi = RECORDED_ATTENTION_CORE_SHARE
    assert lo <= tracescopes.attention_core_share(red) <= hi
    names = {n for n, *_ in doc["host_spans"]}
    assert {"synth.wave.admit", "synth.wave.pack", "synth.wave.dispatch",
            "synth.device.scan", "synth.wave.retire"} <= names
