"""Operations and bytes of the denoiser and its kernels, from shapes alone.

The yardstick for every roofline and utilization the benchmark reports.
Nothing here looks at how the program computes: a count is the work the
algorithm needs, so a fused and an unfused path read the same work.

* FLOPs count 2·M·N·K per matrix product.  One DiT forward per sequence
  (Peebles & Xie, arXiv:2212.09748) is about 24·S·d² + 4·S²·d per layer:
  46.1 GFLOP for DiT-B/2 and 238 GFLOP for DiT-XL/2 at 256 latent tokens,
  twice the paper's 23.0 and 118.6 GMACs.
* Bytes of a call are the weights read once plus the call's own inputs
  and outputs, at the configuration's dtype.  Bytes of one matrix product
  are its two operands and its result.

A ``spec`` is the ``model`` dict of a configuration file: ``d_model``,
``num_layers``, ``num_heads``, ``patch``, ``latent_size``, ``channels``,
``cond_dim``, ``mlp_ratio`` and ``dtype``.
"""
from __future__ import annotations

import json
from pathlib import Path

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip of ``device_kind``.  A
    device that is not in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table holds {sorted(table)}")
    return table[device_kind]


def tokens(spec: dict) -> int:
    """Latent patch tokens of one image (the conditioning token aside)."""
    return (spec["latent_size"] // spec["patch"]) ** 2


def seq_len(spec: dict) -> int:
    """Tokens one sequence carries through the blocks: the patches and
    the conditioning token."""
    return tokens(spec) + 1


def matmuls(spec: dict, seqs: int = 1) -> list[tuple[str, int, int, int]]:
    """Every matrix product of one forward over ``seqs`` sequences, as
    ``(name, M, N, K)``, in the order the forward runs them.  The
    attention products are per (sequence, head), with the count folded
    into M."""
    d, L, h = spec["d_model"], spec["num_layers"], spec["num_heads"]
    n, S = tokens(spec), seq_len(spec)
    pd = spec["patch"] ** 2 * spec["channels"]
    ff = spec["mlp_ratio"] * d
    c = spec["cond_dim"]
    hd = d // h
    out = [("patch_in", seqs * n, d, pd),
           ("t_mlp1", seqs, d, d), ("t_mlp2", seqs, d, d),
           ("y_proj", seqs, d, c), ("cond_tok", seqs, d, c)]
    for i in range(L):
        out += [(f"block{i}.mod", seqs, 6 * d, d),
                (f"block{i}.qkv", seqs * S, 3 * d, d),
                (f"block{i}.qk", seqs * h * S, S, hd),
                (f"block{i}.pv", seqs * h * S, hd, S),
                (f"block{i}.wo", seqs * S, d, d),
                (f"block{i}.mlp_up", seqs * S, ff, d),
                (f"block{i}.mlp_down", seqs * S, d, ff)]
    out += [("out_mod", seqs, 2 * d, d), ("patch_out", seqs * n, pd, d)]
    return out


def is_attention(name: str) -> bool:
    return name.endswith((".qk", ".pv"))


def forward_flops(spec: dict, seqs: int = 1) -> float:
    """FLOPs of one denoiser forward over ``seqs`` sequences."""
    return float(sum(2.0 * M * N * K for _, M, N, K in matmuls(spec, seqs)))


def param_count(spec: dict) -> int:
    """Parameters of the denoiser as the program lays them out."""
    d, L = spec["d_model"], spec["num_layers"]
    n = tokens(spec)
    pd = spec["patch"] ** 2 * spec["channels"]
    ff = spec["mlp_ratio"] * d
    c = spec["cond_dim"]
    top = (pd * d + d) + n * d + 2 * (d * d + d) + (c * d + d) + c \
        + (d * 2 * d + 2 * d) + (d * pd + pd) + (c * d + d)
    block = 3 * d * d + d * d + (d * ff + ff) + (ff * d + d) \
        + (d * 6 * d + 6 * d)
    return top + L * block


def forward_bytes(spec: dict, seqs: int = 1) -> float:
    """Bytes one forward call must move however it is computed: every
    weight read once, and per sequence the noisy latent and its timestep
    and conditioning in, the predicted noise out."""
    b = DTYPE_BYTES[spec["dtype"]]
    latent = spec["latent_size"] ** 2 * spec["channels"]
    io = 2 * latent + spec["cond_dim"] + 1
    return float(b * (param_count(spec) + seqs * io))


def matmul_cost(M: int, N: int, K: int, dtype_bytes: int) -> tuple[float,
                                                                   float]:
    """(FLOPs, bytes) of one M×K by K×N product: operands and result."""
    return 2.0 * M * N * K, float(dtype_bytes * (M * K + K * N + M * N))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def dense_matmul_roofline_s(spec: dict, seqs: int, peak: dict) -> float:
    """Roofline time of one forward's weight matmuls (every product but
    the two attention ones), each product bounded on its own."""
    b = DTYPE_BYTES[spec["dtype"]]
    return sum(roofline_s(*matmul_cost(M, N, K, b), peak)
               for name, M, N, K in matmuls(spec, seqs)
               if not is_attention(name))


# -- the Pallas kernels of the fused path, for their future roofline shares

def cfg_fuse_cost(rows: int, spec: dict) -> tuple[float, float]:
    """One guided ancestral update over ``rows`` latents: reads x, ε_c,
    ε_u and the noise, writes x; about 16 FLOPs an element (combine,
    x̂₀, clip, the update and the row mask)."""
    e = rows * spec["latent_size"] ** 2 * spec["channels"]
    return 16.0 * e, float(DTYPE_BYTES[spec["dtype"]] * 5 * e)


def flash_attention_cost(seqs: int, spec: dict) -> tuple[float, float]:
    """Non-causal attention of one layer over ``seqs`` sequences: the two
    products (4·S²·d FLOPs a sequence) with q, k, v read and o written."""
    d, S = spec["d_model"], seq_len(spec)
    return (4.0 * seqs * S * S * d,
            float(DTYPE_BYTES[spec["dtype"]] * 4 * seqs * S * d))


def adaln_norm_cost(seqs: int, spec: dict, tok: int | None = None
                    ) -> tuple[float, float]:
    """One LayerNorm + (1+scale)·x̂+shift site: about 8 FLOPs an element,
    the tokens read and written once and the per-sequence scale and
    shift read."""
    d = spec["d_model"]
    t = seq_len(spec) if tok is None else tok
    e = seqs * t * d
    return 8.0 * e, float(DTYPE_BYTES[spec["dtype"]] * (2 * e + 2 * seqs * d))
