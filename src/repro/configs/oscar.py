"""OSCAR experiment configuration — the paper's own hyper-parameters.

Paper settings (Sections IV–V): guidance scale s=7.5, T=50 sampling steps,
10 images generated per (client, category) by default (Table III sweeps
10..50), 6 clients (= #domains), 30 images/category/client for Table I,
ResNet-18 global classifier, single communication round, 512-d CLIP
encodings (so each client uploads C × 512 floats).
"""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class DataConfig:
    num_categories: int = 10          # paper: 60 (NICO++) / 90 / 120; scaled
    num_domains: int = 6              # paper: 6 → one domain per client
    image_size: int = 16              # paper: 224; scaled for CPU (DESIGN §8)
    channels: int = 3
    train_per_cat_dom: int = 30       # images per (category, domain) train
    test_per_cat_dom: int = 8
    # Size of the DM pre-training pool per (category, domain) — disjoint
    # from client data.  0 = pre-train on the union of client shards.
    # Nonzero emulates the paper's asymmetry: Stable Diffusion's knowledge
    # is independent of (and far larger than) any client's local dataset.
    pretrain_pool_per_cat_dom: int = 0
    seed: int = 0


@dataclass(frozen=True)
class DiffusionConfig:
    # DiT denoiser (stands in for Stable Diffusion, DESIGN.md §8)
    d_model: int = 128
    num_layers: int = 4
    num_heads: int = 4
    patch: int = 4
    cond_dim: int = 512               # CLIP text-encoding dim (paper: 512)
    train_timesteps: int = 1000
    sample_timesteps: int = 50        # paper: T = 50
    # The paper fixes s=7.5 for Stable Diffusion.  Our scaled-down DM
    # saturates at that strength (validated in benchmarks/guidance sweep);
    # s=2.0 is the tuned equivalent.  The bench reports both.
    guidance_scale: float = 2.0
    paper_guidance_scale: float = 7.5
    cond_drop_prob: float = 0.1       # classifier-free training drop (Ho & Salimans)
    group_cond_prob: float = 0.4      # train on ȳ group means (DESIGN §8)
    pretrain_steps: int = 2500
    batch_size: int = 128
    lr: float = 3e-4
    schedule: str = "cosine"
    # Fused denoiser (opt-in): route dit_apply's attention through the
    # Pallas flash-attention kernel and its three LN+modulation sites
    # through kernels/adaln_norm.  fp32 fused output matches the naive
    # denoiser within float tolerance (online softmax reorders sums);
    # the default (False) path keeps the einsum chain, except on the TPU
    # for 128 <= S <= 1025 tokens, where it runs kernels/dit_attention.
    use_pallas: bool = False
    # Under the fused path only: run the QKV/MLP matmuls with bf16
    # activations and fp32 accumulation (MXU-native mixed precision).
    bf16_act: bool = False


@dataclass(frozen=True)
class OscarConfig:
    data: DataConfig = field(default_factory=DataConfig)
    diffusion: DiffusionConfig = field(default_factory=DiffusionConfig)
    num_clients: int = 6              # paper: 6
    encoding_dim: int = 512           # paper: 512 params per category
    samples_per_category: int = 10    # paper: 10 (Table III sweeps)
    classifier: str = "resnet18"      # paper main results
    classifier_steps: int = 400
    classifier_lr: float = 1e-3
    classifier_batch: int = 64
    seed: int = 0


def paper_preset() -> OscarConfig:
    """The benchmarks' "paper" preset: the paper's setting scaled to a
    16×16 procedural task.  The paper's clients hold 30 natural 224×224
    images per category, deeply data-poor for the task; matching that
    relative poverty (Local weakest, DM-assisted methods strongest) on
    the simpler procedural task needs proportionally fewer client images.
    The DM's knowledge is client-independent: the disjoint pretrain pool
    stands in for Stable Diffusion's web-scale data.  Table I uses the
    Table-III-optimal 30 samples per category."""
    return OscarConfig(
        data=DataConfig(num_categories=10, train_per_cat_dom=10,
                        test_per_cat_dom=8, pretrain_pool_per_cat_dom=120),
        diffusion=DiffusionConfig(d_model=144, pretrain_steps=6000,
                                  batch_size=128),
        classifier_steps=400,
        samples_per_category=30)
