"""Batched serving demo: prefill a batch of prompts, then autoregressive
decode against the KV cache (the serve path the decode_32k / long_500k
dry-run shapes lower).

Run:  PYTHONPATH=src python examples/serve_decode.py [--arch granite-3-2b]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models.transformer import init_lm

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=True)
    params = init_lm(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (args.batch, args.prompt_len)),
                          jnp.int32)

    max_len = args.prompt_len + args.new_tokens
    prefill = jax.jit(make_prefill_step(cfg, max_len=max_len))
    decode = jax.jit(make_decode_step(cfg))

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": prompts})
    next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
    jax.block_until_ready(next_tok)
    t_prefill = time.time() - t0
    print(f"prefill: {args.batch}x{args.prompt_len} tokens "
          f"in {t_prefill:.2f}s")

    out = [next_tok]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        next_tok, logits, cache = decode(params,
                                         {"tokens": next_tok[:, None]},
                                         cache)
        out.append(next_tok)
    jax.block_until_ready(next_tok)
    dt = time.time() - t0
    total = args.batch * (args.new_tokens - 1)
    print(f"decode: {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s on CPU)")
    gen = jnp.stack(out, axis=1)
    print("sample generations (token ids):")
    for b in range(min(args.batch, 3)):
        print(" ", np.asarray(gen[b])[:16].tolist())


if __name__ == "__main__":
    main()
