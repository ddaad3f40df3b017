"""Batched LM serving smoke: prefill a batch of prompts, then greedily
decode token-by-token against the KV cache.

    PYTHONPATH=src python examples/serve_lm.py --arch demo --tokens 32

Uses the reduced config on CPU.  The ``prefill`` / ``decode_step`` pair
exercised here is the same one ``launch/dryrun.py`` lowers for the
256/512-chip meshes (the ``decode_32k`` shape: one token against a 32k
cache at batch 128).  For serving the *sparse-group lasso path solver*
— request coalescing, session caching, warm-start certificate store —
see ``repro.serve`` and ``examples/serve_sgl.py``.
"""
import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get
from repro.models import build


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="demo")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get(args.arch).reduced()
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(2, cfg.vocab, size=(args.batch, args.prompt_len)),
        jnp.int32,
    )
    max_seq = args.prompt_len + args.tokens

    # prefill: one pass over the prompts, builds the KV cache
    t0 = time.perf_counter()
    prefill = jax.jit(lambda p, t: api.prefill(
        p, t, cache_len=max_seq, dtype=jnp.float32))
    logits, cache = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"arch={args.arch} (reduced): prefill {args.batch}x"
          f"{args.prompt_len} tokens in {t_prefill * 1e3:.1f} ms")

    # greedy decode loop against the cache
    decode = jax.jit(api.decode_step)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for i in range(args.tokens - 1):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    gen = np.stack([np.asarray(t) for t in out], axis=1)

    per_tok = dt / max(args.tokens - 1, 1) * 1e3
    print(f"decoded {args.tokens} tokens/seq x {args.batch} seqs: "
          f"{per_tok:.2f} ms/token (batch)")
    print(f"sample continuation (seq 0): {gen[0][:16].tolist()}")
    assert np.isfinite(per_tok)
    assert gen.shape == (args.batch, args.tokens)
    print("serve smoke OK")


if __name__ == "__main__":
    main()
