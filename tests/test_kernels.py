"""Pallas kernel validation: shape/dtype sweeps in interpret mode against the
pure-jnp ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels import chunk_prefill as cp
from repro.kernels.chunk_prefill import chunk_prefill_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_chunk import ssd_chunk

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,hd", [
    (1, 128, 128, 4, 4, 64),     # MHA
    (2, 256, 256, 8, 2, 64),     # GQA 4x
    (1, 128, 256, 8, 1, 128),    # MQA, cross-length
    (2, 64, 64, 2, 2, 32),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Sq, Sk, H, Hkv, hd, causal, dtype):
    if causal and Sq != Sk:
        pytest.skip("causal requires square for this sweep")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bk=64,
                          interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,Hkv,hd,page,slots", [
    (2, 8, 2, 64, 16, 8),
    (3, 4, 4, 32, 8, 4),
    (1, 16, 2, 128, 32, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, H, Hkv, hd, page, slots, dtype):
    n_pages = B * slots + 3
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd), dtype)
    bt = jax.random.permutation(ks[3], n_pages)[:B * slots] \
        .reshape(B, slots).astype(jnp.int32)
    max_len = page * slots
    seq_lens = jax.random.randint(ks[4], (B,), 1, max_len + 1)
    out = paged_attention(q, kp, vp, bt, seq_lens, page_size=page,
                          interpret=True)
    exp = ref.paged_attention_ref(q, kp, vp, bt, seq_lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,Hkv,hd,page,slots", [
    (2, 8, 2, 64, 16, 8),
    (3, 4, 4, 32, 8, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_inline_splice_matches_scatter(B, H, Hkv, hd, page,
                                                       slots, dtype):
    """The decode-horizon read-your-own-write path: attending with the new
    token's K/V spliced inline (``k_new``/``v_new``) must be BITWISE equal
    to scattering it into the pages first and attending without the splice —
    for the ref oracle and the Pallas kernel alike. The page row under the
    write position holds garbage, proving the splice (not the page) is read.
    """
    n_pages = B * slots + 3
    ks = jax.random.split(KEY, 7)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd), dtype)
    bt = jax.random.permutation(ks[3], n_pages)[:B * slots] \
        .reshape(B, slots).astype(jnp.int32)
    seq_lens = jax.random.randint(ks[4], (B,), 1, page * slots + 1)
    k_new = jax.random.normal(ks[5], (B, Hkv, hd), dtype)
    v_new = jax.random.normal(ks[6], (B, Hkv, hd), dtype)
    # scatter k_new/v_new at position seq_len - 1 (row, offset per batch)
    w = seq_lens - 1
    rows = bt[jnp.arange(B), w // page]
    offs = w % page
    kp_sc = kp.at[rows, offs].set(k_new)
    vp_sc = vp.at[rows, offs].set(v_new)
    exp_ref = ref.paged_attention_ref(q, kp_sc, vp_sc, bt, seq_lens)
    got_ref = ref.paged_attention_ref(q, kp, vp, bt, seq_lens,
                                      k_new=k_new, v_new=v_new)
    np.testing.assert_array_equal(np.asarray(got_ref, np.float32),
                                  np.asarray(exp_ref, np.float32))
    exp_pl = paged_attention(q, kp_sc, vp_sc, bt, seq_lens, page_size=page,
                             interpret=True)
    got_pl = paged_attention(q, kp, vp, bt, seq_lens, page_size=page,
                             interpret=True, k_new=k_new, v_new=v_new)
    np.testing.assert_array_equal(np.asarray(got_pl, np.float32),
                                  np.asarray(exp_pl, np.float32))
    np.testing.assert_allclose(np.asarray(got_pl, np.float32),
                               np.asarray(got_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,C,H,Hkv,hd,page,slots", [
    (2, 4, 4, 2, 8, 4, 4),       # GQA 2x, chunk spans pages
    (3, 8, 6, 2, 16, 8, 3),      # GQA 3x
    (1, 16, 2, 2, 32, 16, 2),    # MHA, chunk == page
    (2, 8, 8, 1, 64, 4, 6),      # MQA, chunk 2x page
    (2, 8, 16, 2, 32, 4, 5),     # GQA 8x (qwen3-32b's group)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_prefill_attention_sweep(B, C, H, Hkv, hd, page, slots, dtype):
    """Chunked-prefill attention vs the jnp oracle: each sequence's chunk
    sits at a random absolute offset into its pages (earlier chunks below,
    causal within), exactly the mid-prompt state the engine drives."""
    n_pages = B * slots + 3
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, C, H, hd), dtype)
    kp = jax.random.normal(ks[1], (n_pages, page, Hkv, hd), dtype)
    vp = jax.random.normal(ks[2], (n_pages, page, Hkv, hd), dtype)
    bt = jax.random.permutation(ks[3], n_pages)[:B * slots] \
        .reshape(B, slots).astype(jnp.int32)
    p0 = jax.random.randint(ks[4], (B,), 0, slots * page - C + 1)
    pos = (p0[:, None] + jnp.arange(C)[None, :]).astype(jnp.int32)
    out = chunk_prefill_attention(q, kp, vp, bt, pos, page_size=page,
                                  interpret=True)
    exp = ref.chunk_prefill_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_chunk_prefill_pad_rows_are_finite():
    """Pad rows (position repeated at 0) must produce finite garbage, not
    NaN — the engine discards them but NaN would poison donated pages."""
    B, C, H, Hkv, hd, page, slots = 2, 4, 4, 2, 8, 4, 3
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, C, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (10, page, Hkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (10, page, Hkv, hd), jnp.float32)
    bt = jnp.ones((B, slots), jnp.int32)
    pos = jnp.zeros((B, C), jnp.int32)        # all-pad sequences
    out = chunk_prefill_attention(q, kp, vp, bt, pos, page_size=page,
                                  interpret=True)
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 64, 2, 16, 8, 16),
    (2, 96, 8, 64, 32, 32),     # non-pow2 seq / chunk interplay
])
def test_ssd_chunk_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, H, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, H, N), jnp.float32)
    out = ssd_chunk(x, dt, A, Bm, Cm, chunk=chunk, block_heads=2,
                    interpret=True)
    exp = ref.ssd_chunk_ref(x, dt, A, Bm, Cm)
    scale = float(np.max(np.abs(np.asarray(exp)))) + 1e-9
    err = np.max(np.abs(np.asarray(out) - np.asarray(exp))) / scale
    assert err < 5e-4, err


def test_ssd_chunk_equals_model_scan():
    """The Pallas kernel and the model's jnp chunked scan agree."""
    from repro.models.mamba2 import _ssd_chunk_scan
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 128, 4, 32, 16
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, H, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, H, N), jnp.float32)
    out = ssd_chunk(x, dt, A, Bm, Cm, chunk=32, block_heads=4, interpret=True)
    exp, _ = _ssd_chunk_scan(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_ops_dispatch_ref_on_cpu():
    from repro.kernels import ops
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    a = ops.flash_attention(q, k, v)             # auto -> ref on CPU
    ops.set_mode("interpret")
    try:
        b = ops.flash_attention(q, k, v, bq=32, bk=32)
    finally:
        ops.set_mode(None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("group,limit", [
    (4, None),         # qwen3-8b: its blocks fit Mosaic's default
    (8, 31 << 20),     # qwen3-32b: twice the rows, its own limit
])
def test_chunk_prefill_vmem_limit(group, limit):
    """A 256-token chunk over 8 KV heads of 128 (bf16, 16-token pages):
    the kernel keeps one block of all C*g query rows, and asks for more
    than the default scoped VMEM only where that block needs it, within a
    v5e core's 128 MiB."""
    R = 256 * group
    need = cp.vmem_bytes(R, 8, 128, 16, 2)
    got = cp.vmem_limit_bytes(R, 8, 128, 16, 2)
    assert got == limit
    if got is None:
        assert need * 5 // 4 <= cp.DEFAULT_SCOPED_VMEM
    else:
        assert cp.DEFAULT_SCOPED_VMEM < need < got \
            <= cp.MAX_SCOPED_VMEM < 128 << 20
    with pytest.raises(ValueError):       # 1024-token chunks at group 8
        cp.vmem_limit_bytes(1024 * 8, 8, 128, 16, 2)
