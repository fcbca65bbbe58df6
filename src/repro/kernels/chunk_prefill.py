"""Pallas TPU chunked-prefill attention — the prefill half of the engine's
fused iteration loop (continuous batching with chunked prefill).

A fixed-width chunk of C prompt tokens per sequence attends to everything
already written to its arena pages — earlier chunks of the same prompt and
the current chunk's own K/V, which the caller scatters into the pages before
attending — under a causal mask on absolute token positions. The fixed
[B, C] query shape is the whole point: every chunk of every prompt reuses
one compiled executable, killing the per-prompt-length recompiles of
monolithic prefill.

Grid (batch, page_slots); the page-slot dimension is innermost/sequential so
online-softmax state persists in VMEM scratch, exactly like
``paged_attention``. The block table and per-sequence visible-KV lengths are
scalar-prefetched and drive the K/V page BlockSpec index maps. GQA: q
[B, C, H, hd] is regrouped outside the kernel to [B, Hkv, C*g, hd] (query
rows r = c*g + sub grouped by KV head), with each row's position alongside
as a [B, 1, C*g] lane vector; K/V pages keep their native [page, Hkv, hd]
layout (never repeated). Inside, scores are [Hkv, page, C*g]: keys on
sublanes and query rows on lanes, since a page (16 tokens) is far narrower
than a 128-lane tile. The output comes back as [B, Hkv, hd, C*g] and is
regrouped to [B, C, H, hd] outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _chunk_kernel(block_table, k_lens, q_ref, qpos_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size: int, n_slots: int,
                  scale: float):
    b = pl.program_id(0)
    s = pl.program_id(1)          # page slot (sequential)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_used = pl.cdiv(k_lens[b], page_size)

    @pl.when(s < n_used)
    def _compute():
        q = q_ref[0]                                   # [Hkv, R, hd]
        k = k_ref[0]                                   # [page, Hkv, hd]
        v = v_ref[0]
        # scores with keys on sublanes and query rows on lanes:
        # [Hkv, page, R] (a page is far narrower than a lane tile)
        sc = jax.lax.dot_general(
            k, q, (((2,), (2,)), ((1,), (0,))),
            preferred_element_type=jnp.float32) * scale
        kpos = s * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(qpos_ref[0] >= kpos, sc, NEG_INF)
        m_prev = m_scr[...]                            # [Hkv, 1, R]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(                      # [Hkv, hd, R]
            v.astype(jnp.float32), p, (((0,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(s == n_slots - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "interpret"))
def chunk_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, block_table: jax.Array,
                            positions: jax.Array, page_size: int = 64,
                            interpret: bool = False) -> jax.Array:
    """q [B, C, H, hd]; {k,v}_pages [n_pages, page_size, Hkv, hd];
    block_table [B, max_slots] int32; positions [B, C] int32 absolute
    positions of the chunk tokens. -> [B, C, H, hd].

    The caller must have scattered this chunk's K/V into the pages already;
    per-sequence visible KV length is ``max(positions) + 1`` (pad rows repeat
    position 0 and attend harmlessly to the first written token).
    """
    B, C, H, hd = q.shape
    Hkv = k_pages.shape[2]
    g = H // Hkv
    R = C * g
    n_slots = block_table.shape[1]
    k_lens = jnp.max(positions, axis=1) + 1
    # head h = kvh*g + sub (jnp.repeat order): query rows r = c*g + sub are
    # grouped by kv head, and each row carries its token's position
    qg = (q.reshape(B, C, Hkv, g, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, R, hd))
    qpos = jnp.repeat(positions, g, axis=1)[:, None, :]       # [B, 1, R]
    grid = (B, n_slots)
    kernel = functools.partial(_chunk_kernel, page_size=page_size,
                               n_slots=n_slots, scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, R, hd), lambda b, s, bt, kl: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda b, s, bt, kl: (b, 0, 0)),
            pl.BlockSpec((1, page_size, Hkv, hd),
                         lambda b, s, bt, kl: (bt[b, s], 0, 0, 0)),
            pl.BlockSpec((1, page_size, Hkv, hd),
                         lambda b, s, bt, kl: (bt[b, s], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, hd, R),
                               lambda b, s, bt, kl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, 1, R), jnp.float32),
            pltpu.VMEM((Hkv, 1, R), jnp.float32),
            pltpu.VMEM((Hkv, hd, R), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, hd, R), q.dtype),
        interpret=interpret)
    out = fn(block_table, k_lens, qg, qpos, k_pages, v_pages)
    return (out.reshape(B, Hkv, hd, C, g).transpose(0, 3, 1, 4, 2)
            .reshape(B, C, H, hd))
