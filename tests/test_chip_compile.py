"""Ahead-of-time compiles for a described TPU v5e, at qwen3-8b widths (and
qwen3-32b's attention for the chunked-prefill kernel).

The chip's own compiler is installed with JAX and compiles for a chip that
is described, not attached: these tests run without one. Interpret-mode
parity (``test_kernels.py``) cannot show what it refuses, such as a block
shape off the (8, 128) tiling or a kernel over its fast-memory limit.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.chunk_prefill import chunk_prefill_attention
from repro.kernels.paged_attention import paged_attention
from repro.models import build_model

# qwen3-8b attention: 32 query heads, 8 KV heads, head_dim 128; serving
# pages of 16 tokens, 8 decode slots, a 2048-token window (128 pages)
B, CHUNK, H, HKV, HD, PAGE, ROWS, WIDTH = 8, 256, 32, 8, 128, 16, 512, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel
    return compiled


@pytest.mark.parametrize("inline", [False, True])
def test_paged_attention_compiles(one_chip, inline):
    s = functools.partial(_spec, one_chip)
    pages = s((ROWS, PAGE, HKV, HD), jnp.bfloat16)
    new = s((B, HKV, HD), jnp.bfloat16)
    _compile(functools.partial(paged_attention, page_size=PAGE),
             s((B, H, HD), jnp.bfloat16), pages, pages,
             s((B, WIDTH), jnp.int32), s((B,), jnp.int32),
             **(dict(k_new=new, v_new=new) if inline else {}))


@pytest.mark.parametrize("lanes", [1, 2, 4, 8, 16])
def test_chunk_prefill_attention_compiles(one_chip, lanes):
    """Every launch width the engine's chunk buckets take on 16 slots."""
    s = functools.partial(_spec, one_chip)
    pages = s((ROWS, PAGE, HKV, HD), jnp.bfloat16)
    _compile(functools.partial(chunk_prefill_attention, page_size=PAGE),
             s((lanes, CHUNK, H, HD), jnp.bfloat16), pages, pages,
             s((lanes, WIDTH), jnp.int32), s((lanes, CHUNK), jnp.int32))


@pytest.mark.parametrize("lanes", [1, 8, 32])
def test_chunk_prefill_attention_compiles_at_group8(one_chip, lanes):
    """qwen3-32b: 64 query heads over the same 8 KV heads, whose 2048 query
    rows a chunk need more than the default scoped VMEM; chunk buckets of
    its 32 slots, ends and middle."""
    s = functools.partial(_spec, one_chip)
    pages = s((ROWS, PAGE, HKV, HD), jnp.bfloat16)
    _compile(functools.partial(chunk_prefill_attention, page_size=PAGE),
             s((lanes, CHUNK, 64, HD), jnp.bfloat16), pages, pages,
             s((lanes, WIDTH), jnp.int32), s((lanes, CHUNK), jnp.int32))


def test_decode_step_paged_compiles(one_chip):
    """The engine's one-token decode program, 2 layers of qwen3-8b."""
    s = functools.partial(_spec, one_chip)
    model = build_model(dataclasses.replace(get_config("qwen3-8b"),
                                            n_layers=2))
    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          model.abstract_params())
    _, n_layers, hkv, hd, dtype = model.paged_kv_layout()
    pages = s((n_layers, ROWS, PAGE, hkv, hd), dtype)
    vec = s((B,), jnp.int32)
    _compile(functools.partial(model.decode_step_paged,
                               attend=functools.partial(paged_attention,
                                                        page_size=PAGE)),
             params, {}, pages, pages, s((B, WIDTH), jnp.int32), vec, vec,
             vec, s((B, 1), jnp.int32), vec)
