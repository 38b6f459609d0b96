"""The one traffic generator: turns a traffic file and a seed into requests.

A traffic file (``traffic/<name>.json``) holds parameters only.  Its
``arrival`` names the arrival process, a module
``arrivals/<arrival>.py`` that gives

* ``requests(traffic, rng, seconds, cond_dim)``: the run's requests,
  sorted by due time, drawn from ``rng`` (seeded by ``--seed``);
* ``submit(svc, req)``: hands one request to the service, returns its
  future;
* ``warm_up(system, svc, traffic, requests, key, cond_dim)``: compiles
  and runs every wave shape the window will dispatch;
* ``window(svc, system, requests, seconds, key, on_close)``: serves the
  measured window and returns its record.

So a mix that reuses an arrival process is one data file, and a new kind
of arrival or request is one new module, with no edit to this file or to
the harness.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

ARRIVALS = Path(__file__).resolve().parent / "arrivals"


@dataclass
class Request:
    due_s: float               # seconds after the window opens
    images: int
    steps: int
    guidance: float
    cond: Optional[np.ndarray]  # (cond_dim,) unit-norm encoding; None = Ø
    category: int = 0


def encodings(rng: np.random.Generator, n: int, dim: int) -> np.ndarray:
    """``n`` unit-norm stand-ins for CLIP category encodings."""
    e = rng.standard_normal((n, dim)).astype(np.float32)
    return e / np.linalg.norm(e, axis=1, keepdims=True)


def arrival(traffic: dict, root: Path = ARRIVALS):
    """The module of the traffic file's arrival process."""
    path = root / f"{traffic['arrival']}.py"
    if not path.is_file():
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}: "
                         f"no {path.name} in {root}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_arrival_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(traffic: dict, seed: int, seconds: float, cond_dim: int,
             root: Path = ARRIVALS) -> list[Request]:
    """The requests of one run, sorted by due time."""
    return arrival(traffic, root).requests(
        traffic, np.random.default_rng(seed), seconds, cond_dim)
