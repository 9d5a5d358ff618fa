"""Serving driver: batched autoregressive decoding with KV caches /
SSM states for any registered architecture (reduced variants run on CPU;
full configs are exercised via the dry-run serve_step lowering).

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m \
      --reduced --batch 4 --steps 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import load_all
from repro.configs.base import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import decoder_lm as dlm


def generate(params, cfg, prompt_tokens, steps: int, max_len: int = 0,
             temperature: float = 0.0, seed: int = 0):
    """Greedy / sampled generation. prompt_tokens: (B, P)."""
    B, P = prompt_tokens.shape
    max_len = max_len or (P + steps)
    cache = dlm.init_cache(cfg, B, max_len)
    step = jax.jit(lambda c, t: dlm.decode_step(params, cfg, c, t))
    # prefill by stepping the prompt (simple serving path; bulk prefill
    # uses forward(return_caches=True))
    logits = None
    for t in range(P):
        logits, cache = step(cache, prompt_tokens[:, t:t + 1])
    out = [prompt_tokens]
    key = jax.random.key(seed)
    tok = None
    for s in range(steps):
        if temperature > 0:
            key, k = jax.random.split(key)
            tok = jax.random.categorical(k, logits[:, -1] / temperature)[:, None]
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tok.astype(jnp.int32))
        logits, cache = step(cache, tok.astype(jnp.int32))
    return jnp.concatenate(out, axis=1)


def main(argv=None):
    from repro.launch.train import reduced_config

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    load_all()
    cfg = reduced_config(get_config(args.arch))
    params = dlm.init_model(cfg, 0)
    prompt = jax.random.randint(jax.random.key(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.time()
    seqs = generate(params, cfg, prompt, args.steps,
                    temperature=args.temperature)
    dt = time.time() - t0
    print(f"arch={cfg.name} generated {seqs.shape} in {dt:.1f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    print(np.asarray(seqs[0]))


if __name__ == "__main__":
    main()
