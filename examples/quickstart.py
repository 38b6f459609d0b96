"""Quickstart: the OSCAR pipeline end to end, minutes-scale on CPU.

    PYTHONPATH=src python examples/quickstart.py

Builds the federated multi-domain dataset, pre-trains (or loads) the
frozen classifier-free DM, runs one OSCAR round (client encodings →
upload → server CFG synthesis → global model), and prints the Table-I-row
metrics + the upload size against FedAvg.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.oscar import DataConfig, DiffusionConfig, OscarConfig
from repro.core.experiment import Experiment
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    ocfg = OscarConfig(
        data=DataConfig(num_categories=5, train_per_cat_dom=10,
                        test_per_cat_dom=5),
        diffusion=DiffusionConfig(pretrain_steps=800, batch_size=64),
        classifier_steps=200,
    )
    exp = Experiment(ocfg)
    oscar = exp.run("oscar")
    fedavg = exp.run("fedavg", rounds=5)
    print("\n-- quickstart summary --")
    print(f"OSCAR : avg acc {oscar['avg']*100:.2f}% | "
          f"upload {oscar['upload_params']:,} params (ONE round)")
    print(f"FedAvg: avg acc {fedavg['avg']*100:.2f}% | "
          f"upload {fedavg['upload_params']:,} params (5 rounds)")
    red = 1 - oscar["upload_params"] / fedavg["upload_params"]
    print(f"communication reduction: {red*100:.2f}%")


if __name__ == "__main__":
    main()
