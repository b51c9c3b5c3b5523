"""Serve a (reduced) assigned-architecture LM with batched requests through
the decode engine — prefill once, then step the KV/SSM caches.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch mamba2-2.7b
      (any of the 10 assigned archs; reduced config so it runs on CPU)
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.compile_cache import enable_compile_cache
from repro.models import init_lm
from repro.serving import DecodeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch))
    print(f"[init] {cfg.name} ({cfg.family}), reduced config, "
          f"embedding={cfg.embedding.kind}")
    params = init_lm(jax.random.PRNGKey(0), cfg)
    engine = DecodeEngine(cfg, params, s_max=args.prompt_len + args.new_tokens)

    rng = np.random.default_rng(0)
    shape = ((args.batch, args.prompt_len, cfg.n_codebooks)
             if cfg.input_mode == "audio_tokens"
             else (args.batch, args.prompt_len))
    prompts = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)

    t0 = time.time()
    res = engine.generate(prompts, args.new_tokens, args.temperature)
    dt = time.time() - t0
    toks = args.batch * args.new_tokens
    print(f"[serve] {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. prefill+compile)")
    print(f"[out] shape {res.tokens.shape}; first row: {res.tokens[0][:24]}...")


if __name__ == "__main__":
    enable_compile_cache()
    main()
