"""Batched-request serving example: prefill a prompt batch, then jit-decode
with a ring-buffer KV cache (sliding-window layers hold O(window) state).

  PYTHONPATH=src python examples/serve_lm.py --arch gemma3-4b --tokens 24
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.steps import make_decode_step
from repro.models.transformer import LM
from repro.obs.timing import monotonic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    decode_fn, lm = make_decode_step(cfg, dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0))
    cache = lm.init_cache(args.batch, args.cache_len, dtype=jnp.float32)
    if cfg.is_encoder_decoder:
        cache["enc_out"] = jnp.zeros(
            (args.batch, cfg.encoder_seq_len, cfg.d_model))
    jit_decode = jax.jit(decode_fn)

    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (args.batch, 1)),
                      jnp.int32)
    # warm up / compile
    tok, cache = jit_decode(params, cache, tok)
    t0 = monotonic()
    out = [np.asarray(tok)[:, 0]]
    for _ in range(args.tokens - 1):
        tok, cache = jit_decode(params, cache, tok)
        out.append(np.asarray(tok)[:, 0])
    dt = monotonic() - t0
    gen = np.stack(out, 1)
    print(f"arch={cfg.name} (reduced) batch={args.batch} "
          f"cache={args.cache_len}")
    print(f"{args.tokens} tokens x {args.batch} reqs in {dt:.2f}s "
          f"({args.tokens*args.batch/dt:.1f} tok/s on 1 CPU core)")
    for b in range(min(args.batch, 2)):
        print(f"req{b}: {gen[b][:16].tolist()}")


if __name__ == "__main__":
    main()
