"""End-to-end driver: train a small LM with the paper's SGL structured
sparsity as a first-class training feature.

    PYTHONPATH=src python examples/train_lm_sgl.py --steps 300

Trains the registry's tiny dense 'demo' transformer on a synthetic
copy-task corpus for a few hundred steps with:

  * AdamW + next-token cross entropy,
  * the SGL two-level prox (train/sgl_regularizer.py) applied to FFN
    neuron groups after each optimizer step — the paper's penalty driving
    *structured* (neuron-level) and unstructured sparsity jointly,
  * checkpoint/restart via ckpt.CheckpointManager (kill it mid-run and
    re-invoke: it resumes from the last checkpoint),
  * group-sparsity telemetry (how many FFN neurons the prox zeroed).
"""
import argparse
import os

import numpy as np

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.ckpt.checkpoint import CheckpointManager
from repro.configs import get
from repro.models import build
from repro.train.train_step import make_train_step
from repro.train.sgl_regularizer import SGLRegConfig, group_sparsity


def synthetic_batch(rng, batch, seq, vocab):
    """Copy task: second half of each sequence repeats the first half."""
    half = seq // 2
    first = rng.integers(2, vocab, size=(batch, half))
    toks = np.concatenate([first, first], axis=1)
    return {"tokens": jnp.asarray(toks, jnp.int32)}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sgl-lam", type=float, default=3e-4)
    ap.add_argument("--sgl-tau", type=float, default=0.3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_sgl_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get("demo").reduced()
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch=demo: {n_params / 1e6:.2f}M params, "
          f"{cfg.n_layers}L d={cfg.d_model}")

    sgl_cfg = SGLRegConfig(lam=args.sgl_lam, tau=args.sgl_tau)
    init_state, train_step = make_train_step(
        api, lr=args.lr, sgl_cfg=sgl_cfg, q_chunk=args.seq
    )
    opt_state = init_state(params)
    train_step = jax.jit(train_step, donate_argnums=(0, 1))

    mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every, keep=2)
    start, restored = mgr.restore_latest((params, opt_state))
    if restored is not None:
        params, opt_state = restored
        print(f"resumed from checkpoint at step {start}")
    start = start or 0

    rng = np.random.default_rng(start)  # deterministic resume
    for step in range(start, args.steps):
        batch = synthetic_batch(rng, args.batch, args.seq, cfg.vocab)
        params, opt_state, metrics = train_step(params, opt_state, batch)
        mgr.maybe_save(step + 1, (params, opt_state))
        if step % 20 == 0 or step == args.steps - 1:
            sp = group_sparsity(params)
            neuron_zero = float(np.mean(list(sp.values()))) if sp else 0.0
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                  f"grad_norm {float(metrics['grad_norm']):.3f}  "
                  f"ffn_neurons_zero {neuron_zero:.1%}")

    final = float(metrics["loss"])
    print(f"\nfinal loss {final:.4f} "
          f"({'converging' if final < 2.0 else 'check hyperparameters'})")


if __name__ == "__main__":
    main()
