"""LM pre-training example: a few hundred steps of any assigned arch
(reduced variant) on a synthetic in-memory token stream, via the same
train-step factory the multi-pod launcher lowers.

    PYTHONPATH=src python examples/train_lm.py --arch xlstm-125m --steps 200
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.train.steps import init_train_state, make_train_step
from repro.utils import enable_compile_cache


def synthetic_tokens(key, n_seq, seq, vocab):
    """Markov-ish synthetic stream: learnable bigram structure."""
    k1, k2 = jax.random.split(key)
    trans = jax.random.dirichlet(k1, jnp.full((vocab,), 0.3), (vocab,))
    toks = [jax.random.randint(k2, (n_seq, 1), 0, vocab)]
    for t in range(seq - 1):
        kt = jax.random.fold_in(k2, t)
        nxt = jax.random.categorical(kt, jnp.log(trans[toks[-1][:, 0]] + 1e-9))
        toks.append(nxt[:, None])
    return jnp.concatenate(toks, axis=1)


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = smoke_config(get_config(args.arch))
    key = jax.random.PRNGKey(0)
    print(f"[train] {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")
    state = init_train_state(key, cfg)
    step = jax.jit(make_train_step(cfg, lr=1e-3))

    if cfg.frontend == "token":
        data = synthetic_tokens(key, 256, args.seq, cfg.vocab_size)
        def batch_at(i):
            idx = jax.random.randint(jax.random.fold_in(key, i),
                                     (args.batch,), 0, data.shape[0])
            return {"tokens": data[idx]}
    elif cfg.frontend == "audio_frames":
        def batch_at(i):
            k = jax.random.fold_in(key, i)
            return {"frames": jax.random.normal(k, (args.batch, args.seq,
                                                    cfg.frontend_dim)),
                    "mask": jax.random.bernoulli(k, 0.3, (args.batch, args.seq)),
                    "labels": jax.random.randint(k, (args.batch, args.seq), 0,
                                                 cfg.vocab_size)}
    else:
        P = cfg.num_prefix_tokens
        def batch_at(i):
            k = jax.random.fold_in(key, i)
            return {"patches": jax.random.normal(k, (args.batch, P,
                                                     cfg.frontend_dim)),
                    "tokens": jax.random.randint(k, (args.batch, args.seq - P),
                                                 0, cfg.vocab_size)}

    t0 = time.time()
    first = last = None
    for i in range(args.steps):
        state, metrics = step(state, batch_at(i))
        if i == 0:
            first = float(metrics["loss"])
        if i % 50 == 0 or i == args.steps - 1:
            print(f"  step {i:4d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.2f}", flush=True)
        last = float(metrics["loss"])
    print(f"[train] {args.steps} steps in {time.time()-t0:.1f}s; "
          f"loss {first:.3f} -> {last:.3f} "
          f"({'LEARNING' if last < first else 'no improvement'})")


if __name__ == "__main__":
    main()
