"""Serving example: prefill a prompt then greedy-decode with the KV-cache /
recurrent-state runtime, for any assigned architecture (reduced variant on
CPU).

    PYTHONPATH=src python examples/serve_decode.py --arch jamba-1.5-large-398b
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.models.attention import KVCache
from repro.models.transformer import forward, init_lm
from repro.serve.steps import make_serve_step
from repro.utils import enable_compile_cache


def pad_kv(caches, max_len):
    def pad_leaf(c):
        if isinstance(c, KVCache):
            pad = max_len - c.k.shape[2]
            return KVCache(
                jnp.pad(c.k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
                jnp.pad(c.v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))))
        return c
    return {k: pad_leaf(v) for k, v in caches.items()}


def main():
    enable_compile_cache(Path(__file__).resolve().parents[1])
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--batch", type=int, default=2)
    args = ap.parse_args()

    cfg = smoke_config(get_config(args.arch))
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step "
                         "(DESIGN.md §5)")
    key = jax.random.PRNGKey(0)
    print(f"[serve] {cfg.name}: {cfg.num_layers}L d={cfg.d_model}")
    params = init_lm(key, cfg)

    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    total = args.prompt_len + args.new_tokens
    t0 = time.time()
    logits, _, caches = forward(params, cfg, {"tokens": prompt},
                                mode="prefill")
    caches = pad_kv(caches, total)
    print(f"[serve] prefill {args.prompt_len} tokens in {time.time()-t0:.2f}s")

    serve_step = jax.jit(make_serve_step(cfg))
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None].astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.new_tokens - 1):
        tok, _, caches = serve_step(params, tok, caches,
                                    jnp.int32(args.prompt_len + i))
        out.append(tok)
    toks = jnp.concatenate(out, axis=1)
    dt = time.time() - t0
    print(f"[serve] decoded {args.new_tokens} tokens in {dt:.2f}s "
          f"({args.new_tokens/dt:.1f} tok/s/seq, batch {args.batch})")
    print("[serve] sample token ids:", toks[0].tolist())


if __name__ == "__main__":
    main()
