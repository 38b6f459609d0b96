"""End-to-end driver: the full paper experiment — all seven methods on the
feature-skew federated benchmark, with per-client accuracy and upload
accounting (paper Tables I + IV, Fig. 1).

    PYTHONPATH=src python examples/oscar_federated.py [--preset quick|paper]
                                                      [--methods oscar,fedavg]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.oscar import DataConfig, DiffusionConfig, OscarConfig
from repro.core.experiment import ALL_METHODS, Experiment
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="quick", choices=("quick", "paper"))
    ap.add_argument("--methods", default=",".join(ALL_METHODS))
    args = ap.parse_args()

    if args.preset == "quick":
        ocfg = OscarConfig(
            data=DataConfig(num_categories=5, train_per_cat_dom=10,
                            test_per_cat_dom=5),
            diffusion=DiffusionConfig(pretrain_steps=800, batch_size=64),
            classifier_steps=200)
    else:
        ocfg = OscarConfig()

    exp = Experiment(ocfg)
    results = {}
    for m in args.methods.split(","):
        results[m] = exp.run(m)

    print(f"\n{'method':10s} {'avg acc':>8s} {'upload params':>14s}")
    for m, r in sorted(results.items(), key=lambda kv: -kv[1]["avg"]):
        print(f"{m:10s} {r['avg']*100:7.2f}% {r['upload_params']:>14,}")


if __name__ == "__main__":
    main()
