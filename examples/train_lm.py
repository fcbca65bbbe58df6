"""End-to-end training driver: train a ~100M-param qwen3-family model for a
few hundred steps on synthetic token data with the full production loop —
AdamW, microbatching, checkpoint/restart (kill-and-resume), and straggler
detection hooks.

  PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import latest_step, restore, save
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.distributed.fault import StragglerDetector
from repro.models import build_model
from repro.training import OptConfig, adamw_init, make_train_step


def synthetic_batch(key, vocab, batch, seq):
    """Markov-ish synthetic LM data: next token = (3x + 7) % vocab + noise."""
    base = jax.random.randint(key, (batch, 1), 0, vocab)
    steps = jnp.arange(seq)[None, :]
    toks = (base * 3 + 7 * steps) % vocab
    noise = jax.random.bernoulli(key, 0.05, toks.shape)
    rand = jax.random.randint(key, toks.shape, 0, vocab)
    toks = jnp.where(noise, rand, toks).astype(jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=129)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--n-micro", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()

    # ~100M-class config: qwen3 family, scaled down
    cfg = dataclasses.replace(
        get_config("qwen3-8b"), n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=4, head_dim=64, d_ff=1536, vocab=2048,
        dtype=jnp.float32, name="qwen3-100m")
    model = build_model(cfg)
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps, microbatch x{args.n_micro}")

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=20)
    opt = adamw_init(params)
    start = 0
    if latest_step(args.ckpt) is not None:   # fault-tolerant restart
        (params, opt), extra = restore(args.ckpt, (params, opt))
        start = extra["step"]
        print(f"[train] resumed from checkpoint at step {start}")

    step_fn = jax.jit(make_train_step(model, opt_cfg, n_micro=args.n_micro))
    sd = StragglerDetector()
    t_start = time.time()
    for step in range(start, args.steps):
        k = jax.random.fold_in(key, step)
        batch = synthetic_batch(k, cfg.vocab, args.batch, args.seq)
        t0 = time.time()
        params, opt, m = step_fn(params, opt, batch)
        sd.observe(0, time.time() - t0)
        if step % 25 == 0 or step == args.steps - 1:
            print(f"  step {step:4d}: loss={float(m['loss']):.4f} "
                  f"lr={float(m['lr']):.5f} "
                  f"({(time.time()-t0)*1e3:.0f}ms/step)")
        if step and step % 100 == 0:
            save(args.ckpt, (params, opt), step=step,
                 extra={"step": step}, async_=True)
    save(args.ckpt, (params, opt), step=args.steps,
         extra={"step": args.steps})
    tput = args.batch * (args.seq - 1) * (args.steps - start) \
        / (time.time() - t_start)
    print(f"[train] done: final loss {float(m['loss']):.4f}, "
          f"{tput:.0f} tok/s on CPU; checkpoint at {args.ckpt}")


if __name__ == "__main__":
    main()
