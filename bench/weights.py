"""Random weights for the served model, drawn on the device from the seed.

The benchmark, not the program, makes the weights: the plain reference then
reads the same arrays without taking anything the program made. The tree
follows the program's parameter layout (``Model.abstract_params()``: names,
shapes and dtypes), and every leaf is drawn in the dtype it is served in,
in one jitted call per device.

Values: matrices N(0, 1/fan_in), the embedding N(0, 1), norm scales
1 + N(0, 0.1^2) (so a wrong norm shows in the logits). The rows of the
embedding and the columns of the LM head past the published vocabulary
(the program pads the vocabulary to a multiple of 256) are zero: their
logits are exactly 0, far below the best of the real vocabulary, so no
served token can come from the padding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("ln", "qn", "kn", "final_ln")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf(key, path, struct, vocab: int):
    name = str(getattr(path[-1], "key", path[-1]))
    shape, dt = struct.shape, struct.dtype
    if name in NORMS:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dt)
    if name == "embed":
        x = jax.random.normal(key, shape, dt)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return jnp.where(rows < vocab, x, jnp.zeros((), dt))
    fan_in = shape[-2]
    x = jax.random.normal(key, shape, dt) * jnp.asarray(fan_in ** -0.5, dt)
    if name == "lm_head":
        cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        x = jnp.where(cols < vocab, x, jnp.zeros((), dt))
    return x


def draw(abstract_params, seed: int, vocab: int, device) -> dict:
    """The weight tree on ``device``, drawn from ``seed`` in one call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_params)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(k, p, s, vocab)
                      for k, (p, s) in zip(keys, leaves)])

    out = jax.jit(make, out_shardings=jax.sharding.SingleDeviceSharding(
        device))(jax.device_put(seed_key(seed), device))
    return jax.block_until_ready(out)


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))
