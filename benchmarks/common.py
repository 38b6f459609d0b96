"""Shared benchmark plumbing: one cached Experiment per config, CSV/table
printing, result persistence."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

RESULTS = Path(__file__).resolve().parent / "results"
RESULTS.mkdir(exist_ok=True)

_EXPERIMENT = {}


def _timed(fn, *args, **kw):
    """Run ``fn(*args, **kw)`` and return ``(seconds, result)`` measured
    on the monotonic ``time.perf_counter`` clock — wall timings must
    never ride ``time.time()``, which steps under NTP adjustments."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return time.perf_counter() - t0, out


def get_experiment(preset: str = "paper"):
    """Cached Experiment (data + pre-trained frozen DM)."""
    from repro.configs.oscar import (DataConfig, DiffusionConfig, OscarConfig,
                                     paper_preset)
    if preset in _EXPERIMENT:
        return _EXPERIMENT[preset]
    if preset == "quick":
        ocfg = OscarConfig(
            data=DataConfig(num_categories=5, train_per_cat_dom=8,
                            test_per_cat_dom=4),
            diffusion=DiffusionConfig(pretrain_steps=600, batch_size=64),
            classifier_steps=150)
    else:
        ocfg = paper_preset()
    from repro.core.experiment import Experiment
    _EXPERIMENT[preset] = Experiment(ocfg)
    return _EXPERIMENT[preset]


def print_table(title: str, rows: list[dict], cols: list[str]):
    print(f"\n### {title}")
    widths = {c: max(len(c), *(len(_fmt(r.get(c, ""))) for r in rows))
              for c in cols}
    print(" | ".join(c.ljust(widths[c]) for c in cols))
    print("-|-".join("-" * widths[c] for c in cols))
    for r in rows:
        print(" | ".join(_fmt(r.get(c, "")).ljust(widths[c]) for c in cols))
    sys.stdout.flush()


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.2f}"
    return str(v)


def save_result(name: str, obj):
    (RESULTS / f"{name}.json").write_text(json.dumps(obj, indent=1,
                                                     default=str))


def acc_row(method: str, metrics: dict, num_clients: int = 6) -> dict:
    row = {"model": method}
    for r in range(num_clients):
        k = f"client{r + 1}"
        if k in metrics:
            row[k] = metrics[k] * 100
    row["avg"] = metrics["avg"] * 100
    return row
