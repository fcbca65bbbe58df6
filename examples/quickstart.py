"""Quickstart: build any of the 10 assigned architectures, run a forward /
train step, then serve a few requests through the continuous-batching engine
with Maestro's memory accounting.

  PYTHONPATH=src python examples/quickstart.py --arch qwen3-8b
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, list_configs
from repro.core.runtime.accounting import MemoryAccountant
from repro.models import build_model
from repro.serving.engine import Engine, Request
from repro.training import OptConfig, adamw_init, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list_configs())
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    print(f"[quickstart] {cfg.name}: {cfg.param_count()/1e9:.1f}B params "
          f"({cfg.family}); running the REDUCED smoke config on CPU")
    cfg = cfg.reduced()
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"[quickstart] reduced model: {n/1e6:.1f}M params")

    # --- a few train steps -------------------------------------------------
    toks = jax.random.randint(key, (4, 64), 0, cfg.vocab)
    extras = {}
    if cfg.encoder is not None:
        extras["frames"] = jax.random.normal(
            key, (4, cfg.encoder.n_frames, cfg.d_model), cfg.dtype)
    if cfg.cross_attn is not None and cfg.family == "vlm":
        extras["ctx_embeds"] = jax.random.normal(
            key, (4, cfg.cross_attn.n_ctx_tokens, cfg.d_model), cfg.dtype)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1), **extras}
    step = jax.jit(make_train_step(model, OptConfig(lr=1e-3, warmup_steps=1)))
    opt = adamw_init(params)
    for i in range(args.steps):
        params, opt, m = step(params, opt, batch)
        print(f"  step {i}: loss={float(m['loss']):.4f} "
              f"gnorm={float(m['grad_norm']):.3f}")

    # --- serve through the engine ------------------------------------------
    acc = MemoryAccountant(m_total=256e6)
    eng = Engine(model, params, acc, max_slots=2, s_max=96)
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(req_id=i, extras=extras and {
            k: v[:1] for k, v in extras.items()},
            tokens=list(rng.integers(0, cfg.vocab, 12)), max_new=8))
    done = eng.drain()
    for r in done:
        print(f"  request {r.req_id}: generated {r.out}")
    print(f"[quickstart] OK — KV accountant headroom "
          f"{acc.headroom/1e6:.0f}MB, invariant={acc.check_invariant()}")


if __name__ == "__main__":
    main()
