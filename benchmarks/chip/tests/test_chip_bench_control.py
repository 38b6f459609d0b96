"""The correctness comparison rejects what it has to, at a tiny width on
the CPU: the control (the plain reference computed in bfloat16, one
precision below the stated float32, put in the program's place) and
answers altered where the program makes them, each with every other
part of a run left as it is."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_util import CPU_PEAK, run_cell, tiny_checkout

import repro.serve.synthesis as synthesis
from benchmarks.chip import calibrate, harness


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_checkout(tmp_path_factory.mktemp("bench"))


def test_bfloat16_control_and_shifted_rows_come_out_not_correct(checkout):
    """The calibration's own readings: on every seed the program is
    correct, and the control and the rows handed to the wrong requests,
    judged by the run's own function, are not."""
    cell = harness.load_cell(checkout, "b2.round_drain")
    got = calibrate.readings(cell, [3, 2 ** 31 + 17], 1.0, run_kw=dict(
        devices=jax.devices(), peak=CPU_PEAK, log=lambda *a: None))
    limit = cell.limits["limits"]["row_gap_excess"]
    assert got["program_max"] <= limit
    assert got["control_min"] > limit and got["fault_min"] > limit


def _ragged(real, fault):
    def altered(params, dc, sched, y, row_keys, guidance, num_steps, **kw):
        if fault == "guidance_dropped":
            guidance = jnp.zeros_like(guidance)
        elif fault == "noise_of_another_row":
            row_keys = jnp.roll(row_keys, 1, axis=0)
        x = real(params, dc, sched, y, row_keys, guidance, num_steps, **kw)
        return jnp.roll(x, 1, axis=0) if fault == "rows_shifted" else x
    return altered


@pytest.mark.parametrize("fault", ["rows_shifted", "guidance_dropped",
                                   "noise_of_another_row"])
def test_answer_altered_where_produced_is_caught(checkout, fault,
                                                 monkeypatch):
    """Each wave's rows come back altered inside the sampler the engine
    calls: handed to the neighbouring row, made without guidance, or
    made from the neighbouring row's noise."""
    monkeypatch.setattr(synthesis, "sample_cfg_ragged",
                        _ragged(synthesis.sample_cfg_ragged, fault))
    out = run_cell(checkout, "b2.round_drain", seed=2 ** 31 + 11)
    assert out["correct"] is False
    c = out["checks"]["row_gap_excess"]
    assert c["value"] > c["limit"]


def test_gap_excess_reads_zero_at_the_stated_precision():
    rng = np.random.default_rng(0)
    want = rng.uniform(-1, 1, (6, 4, 4, 2))
    stated = want + rng.normal(0, 0.02, want.shape)
    assert harness.gap_excess(want + (stated - want)[::-1], want,
                              stated) == pytest.approx(0.0, abs=1e-9)
    assert harness.gap_excess(want + 3 * (stated - want), want,
                              stated) == pytest.approx(2.0, rel=1e-9)
    # both sides below round-off read in its unit: a CPU run reads 0
    assert harness.gap_excess(want, want, want) == 0.0


def test_sample_holds_the_largest_request_and_no_row_twice():
    from benchmarks.chip.generator import Request
    sub = {r: Request(0.0, 10 if r != 4 else 12, 50, 7.5, None)
           for r in range(6)}
    win = {"in_window": list(sub), "submitted": sub}
    picks = harness.sample(win, {"sample_rows": 32}, 2 ** 31 + 1)
    assert len(picks) == len(set(picks)) == 32
    assert picks[0][0] == 4
    assert all(0 <= i < sub[r].images for r, i in picks)
    assert picks == harness.sample(win, {"sample_rows": 32}, 2 ** 31 + 1)
