"""End-to-end driver: train a ~100M-parameter llama-family model for a few
hundred steps on the synthetic LM stream with Byzantine workers and
Bulyan(Krum) aggregation, with checkpointing.

    PYTHONPATH=src python examples/train_llm.py --steps 200

The model is a 12-layer, d=768 llama3-style decoder (~100M params) with
heads derived from ``--d-model`` (d/64 heads), so it cannot express a
published configuration's widths; ``chip_smoke.py`` drives the same step
at ``llama3_2_3b``'s widths.  One training step is the full production
path: per-worker gradients -> in-graph omniscient attack -> distributed
Bulyan -> AdamW, with n = 7 workers (f = 1).  It runs on whatever backend
JAX finds; on a CPU a 100M step takes about a minute, so pass
``--d-model 384 --steps 150`` there for a ~25M run with identical
mechanics.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.data.synthetic import lm_batches
from repro.agg import AggSpec
from repro.dist.train import init_agg_state, make_train_step
from repro.launch.device import enable_compile_cache
from repro.models import init_model
from repro.models.config import ModelConfig
from repro.optim import get_optimizer


def model_100m() -> ModelConfig:
    return ModelConfig(
        name="llama-100m", arch_type="dense",
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
        d_ff=2048, vocab_size=32768, head_dim=64,
        ffn_act="swiglu", layer_pattern=("attn",),
        tie_embeddings=True, param_dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--workers", type=int, default=7)
    ap.add_argument("--f", type=int, default=1)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--gar", default="bulyan-krum")
    ap.add_argument("--stream-vocab", type=int, default=2048,
                    help="vocab of the synthetic Markov stream (smaller "
                         "than the model's 32768 so a few hundred steps "
                         "visibly reduce loss)")
    ap.add_argument("--attack", default="omniscient_linf")
    ap.add_argument("--ckpt", default="artifacts/llm_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    import dataclasses
    cfg = dataclasses.replace(model_100m(), d_model=args.d_model,
                              n_layers=args.layers,
                              n_heads=args.d_model // 64,
                              n_kv_heads=max(2, args.d_model // 192))
    key = jax.random.PRNGKey(0)
    params = init_model(key, cfg)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"model: {cfg.name}, {n_params / 1e6:.1f}M params; "
          f"n={args.workers} workers (f={args.f}), gar={args.gar}, "
          f"attack={args.attack}")

    opt = get_optimizer("adamw", 3e-4, weight_decay=0.01)
    state = opt.init(params)
    start = 0
    if args.resume and os.path.exists(os.path.join(args.ckpt,
                                                   "manifest.json")):
        params, start = load_checkpoint(args.ckpt, params)
        print(f"resumed from step {start}")

    spec = AggSpec(f=args.f, gar=args.gar, attack=args.attack)
    step = jax.jit(make_train_step(cfg, spec, opt))
    # stateful GARs (buffered-*, centered_clip_momentum) carry an AggState
    agg_state = init_agg_state(spec, params, args.workers)

    n, b, s = args.workers, args.batch, args.seq
    t0 = time.time()
    for t in range(start, start + args.steps):
        toks, labs = [], []
        for w in range(n):
            x, y = lm_batches(args.stream_vocab, b, s, t * n + w, seed=7)
            toks.append(x)
            labs.append(y)
        batch = {"tokens": jnp.asarray(np.stack(toks)),
                 "labels": jnp.asarray(np.stack(labs))}
        if agg_state is not None:
            params, state, m, agg_state = step(params, state, batch,
                                               agg_state)
        else:
            params, state, m = step(params, state, batch)
        if t % 10 == 0 or t == start + args.steps - 1:
            dt = time.time() - t0
            tok_s = (t - start + 1) * n * b * s / max(dt, 1e-9)
            print(f"step {t:4d}  loss {float(m['loss']):.4f}  "
                  f"|g| {float(m['grad_norm']):.3f}  "
                  f"byz_w {float(m.get('byz_weight', 0)):.1f}  "
                  f"{tok_s:,.0f} tok/s", flush=True)
    save_checkpoint(args.ckpt, params, step=start + args.steps)
    print(f"checkpoint saved to {args.ckpt}")


if __name__ == "__main__":
    main()
