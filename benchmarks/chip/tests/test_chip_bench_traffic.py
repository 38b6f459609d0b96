"""The traffic generator and the weights: the same work for every seed,
data drawn from the seed."""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench_util import ROOT

from benchmarks.chip import generator

TRAFFIC = ROOT / "benchmarks" / "chip" / "traffic"


def _load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def test_backlog_is_the_round_in_upload_order():
    t = _load("round_drain")
    reqs = generator.generate(t, 5, 50.0, 16)
    assert len(reqs) == 6 * 60
    assert all(r.due_s == 0 and r.images == 10 and r.steps == 50
               and r.guidance == 7.5 for r in reqs)
    assert [r.category for r in reqs[:61]] == list(range(60)) + [0]
    for r in reqs:
        assert abs(np.linalg.norm(r.cond) - 1) < 1e-5


def test_backlog_same_work_other_encodings_for_every_seed():
    t = _load("round_drain")
    a = generator.generate(t, 1, 50.0, 16)
    b = generator.generate(t, 2 ** 31 + 3, 50.0, 16)
    shape = lambda reqs: [(r.images, r.steps, r.guidance, r.category)
                          for r in reqs]
    assert shape(a) == shape(b)
    assert not np.allclose(a[0].cond, b[0].cond)
    c = generator.generate(t, 2 ** 31 + 3, 50.0, 16)
    assert all(np.array_equal(x.cond, y.cond) for x, y in zip(b, c))


def test_unknown_arrival_process_raises():
    with pytest.raises(ValueError, match="unknown arrival"):
        generator.generate({"arrival": "nowhere"}, 1, 1.0, 16)


def test_weights_from_the_seed_none_zero():
    import jax

    from benchmarks.chip.families import dit
    spec = dict(d_model=32, num_layers=2, num_heads=2, patch=2,
                latent_size=8, channels=4, cond_dim=16, mlp_ratio=4,
                dtype="float32")
    a, b = dit.make_params(spec, 2 ** 31 - 2), dit.make_params(spec, 7)
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert all(np.abs(np.asarray(x)).min() > 0 for x in la)
    assert all(not np.array_equal(x, y) for x, y in zip(la, lb))
    again = jax.tree.leaves(dit.make_params(spec, 7))
    assert all(np.array_equal(x, y) for x, y in zip(lb, again))
    w = np.asarray(a["blocks"][0]["w_up"]["w"])
    assert abs(w.std() * np.sqrt(32) - 1) < 0.1
    assert abs(np.linalg.norm(np.asarray(a["null_y"])) - 1) < 1e-5
