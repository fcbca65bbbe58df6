"""Iteration-level continuous batching with chunked prefill.

The parity contract is OUTPUT-LEVEL: per-request greedy token sequences from
the chunked engine must equal the monolithic path exactly (prefix cache on
and off, every zoo model with self-attention KV). Logits are allowed to
drift at ulp level — fixed-shape padded reductions reassociate differently
than per-length monolithic prefill — which greedy argmax absorbs.

Chunk launches are lane-compacted: a launch packs the prompts that advance
into its first lanes and is as wide as the smallest bucket that holds them
(:func:`chunk_buckets`), every bucket compiled before the first launch.

Also pins the satellite contracts of the same PR: deque-backed waiting
queue with preserved requeue semantics, ``EngineStalledError`` from an
exhausted drain, ``step()`` returning only newly-finished requests, and the
compile-count telemetry staying flat across distinct prompt lengths.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.runtime.accounting import MemoryAccountant
from repro.models import build_model
from repro.serving import telemetry
from repro.serving.engine import (Engine, EngineStalledError, Request,
                                  chunk_buckets)
from repro.serving.kv_arena import KVArena

CHUNK_ZOO = ("qwen3-8b", "starcoder2-15b")     # self-attention KV models


@pytest.fixture(scope="module", params=CHUNK_ZOO)
def zoo_model(request):
    cfg = get_config(request.param).reduced()
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab, p))
            for p in (3, 7, 12, 5, 9, 14)]


def _drain_all(m, params, prompts, *, chunk, prefix_cache=False,
               sequential=False, max_new=6, max_slots=3, **kw):
    eng = Engine(m, params, MemoryAccountant(m_total=512e6),
                 max_slots=max_slots, s_max=64, kv_backend="ref",
                 prefix_cache=prefix_cache, prefill_chunk_tokens=chunk, **kw)
    out = {}
    if sequential:        # drain between prompts so later ones hit the index
        for i, p in enumerate(prompts):
            eng.submit(Request(req_id=i, tokens=list(p), max_new=max_new))
            for r in eng.drain():
                out[r.req_id] = r
    else:
        for i, p in enumerate(prompts):
            eng.submit(Request(req_id=i, tokens=list(p), max_new=max_new))
        for r in eng.drain():
            out[r.req_id] = r
    return eng, out


# ------------------------------------------------------- output-level parity
def test_chunked_matches_monolithic_every_zoo_model(zoo_model):
    cfg, m, params = zoo_model
    assert m.supports_chunked_prefill
    prompts = _prompts(cfg)
    _, mono = _drain_all(m, params, prompts, chunk=0)
    for chunk in (4, 8, 16):
        _, chk = _drain_all(m, params, prompts, chunk=chunk)
        assert {k: r.out for k, r in chk.items()} == \
               {k: r.out for k, r in mono.items()}, f"chunk={chunk}"


def test_chunked_matches_monolithic_with_prefix_cache(tiny):
    cfg, m, params = tiny
    rng = np.random.default_rng(5)
    base = list(rng.integers(0, cfg.vocab, 40))
    prompts = [base,                          # indexes 2 full pages
               base[:32] + [3, 1, 4, 1, 5],   # hits both full pages
               base[:16] + [9] * 20,          # hits page 0 only
               base[:20] + [7] * 11]          # partial-page COW hit
    _, mono = _drain_all(m, params, prompts, chunk=0, sequential=True,
                         max_slots=2)
    for pc in (False, True):
        for chunk in (4, 16):
            eng, chk = _drain_all(m, params, prompts, chunk=chunk,
                                  prefix_cache=pc, sequential=True,
                                  max_slots=2)
            assert {k: r.out for k, r in chk.items()} == \
                   {k: r.out for k, r in mono.items()}, (pc, chunk)
            if pc:   # suffix chunks resumed AFTER the cached prefix pages
                assert [chk[k].prefill_avoided for k in sorted(chk)] == \
                       [0, 32, 16, 20]
            assert eng.arena.check_mirror()


def test_ssm_model_falls_back_to_monolithic():
    """A model without self-attention KV cannot chunk — the knob degrades
    to monolithic prefill instead of failing."""
    cfg = get_config("mamba2-2.7b").reduced()
    m = build_model(cfg)
    assert not m.supports_chunked_prefill
    params = m.init(jax.random.PRNGKey(0))
    prompts = _prompts(cfg)[:2]
    _, mono = _drain_all(m, params, prompts, chunk=0, max_new=4)
    eng, chk = _drain_all(m, params, prompts, chunk=8, max_new=4)
    assert eng.chunk_tokens == 0
    assert {k: r.out for k, r in chk.items()} == \
           {k: r.out for k, r in mono.items()}


# ----------------------------------------------- compile counter / telemetry
def test_prefill_compile_count_flat_across_prompt_lengths(tiny):
    cfg, m, params = tiny
    prompts = _prompts(cfg)
    assert len({len(p) for p in prompts}) == 6     # six distinct lengths
    mono_eng, _ = _drain_all(m, params, prompts, chunk=0)
    chk_eng, _ = _drain_all(m, params, prompts, chunk=4)
    assert mono_eng.prefill_compiles == 6          # one trace per length
    # one chunk program per launch width, whatever the prompt lengths
    assert chk_eng.prefill_compiles == len(chunk_buckets(3)) == 3
    # counters flow through the node snapshot for gateway aggregation
    total = sum(len(p) for p in prompts)
    assert chk_eng.stat_prefill_tokens == total
    assert chk_eng.stat_decode_tokens > 0
    assert chk_eng.stat_fused_steps > 0            # prefill+decode co-ran


def test_engine_counters_exposed_in_node_kv_stats(tiny):
    from repro.serving.node_runtime import NodeRuntime
    cfg, m, params = tiny
    host = jax.tree.map(np.asarray, params)
    node = NodeRuntime(0, 0, {cfg.name: m}, {cfg.name: host},
                       hbm_budget=1.2e9, max_slots=2, s_max=64,
                       prefill_chunk_tokens=4)
    node.submit(cfg.name, Request(req_id=0, tokens=[1, 2, 3, 4, 5],
                                  max_new=4))
    for _ in range(30):
        node.step()
        if not node.has_work():
            break
    st = node.kv_stats()
    assert st["engine_prefill_tokens"] == 5
    assert st["engine_decode_tokens"] > 0
    assert st["engine_prefill_compiles"] == len(chunk_buckets(2)) == 2
    assert st["engine_steps"] > 0


def test_ttft_stamped_on_finished_requests(tiny):
    cfg, m, params = tiny
    _, done = _drain_all(m, params, _prompts(cfg)[:3], chunk=4)
    assert all(r.ttft_s > 0 for r in done.values())
    _, done = _drain_all(m, params, _prompts(cfg)[:3], chunk=0)
    assert all(r.ttft_s > 0 for r in done.values())


# ------------------------------------------------------- lane compaction
@pytest.mark.parametrize("max_slots,buckets", [
    (1, [1]), (2, [1, 2]), (3, [1, 2, 3]), (6, [1, 2, 4, 6]),
    (16, [1, 2, 4, 8, 16])])
def test_chunk_buckets_are_powers_of_two_then_max_slots(max_slots, buckets):
    assert chunk_buckets(max_slots) == buckets


class _ChunkSpans:
    """An annotation factory that keeps the args of every ``engine.chunk``
    span as it opens."""

    def __init__(self):
        self.args = []

    def __call__(self, name, **args):
        if name == "maestro:engine.chunk":
            self.args.append(args)
        return telemetry.NULL_SPAN


@pytest.mark.parametrize("lanes", [1, 2, 3, 5, 6])
def test_compacted_chunk_launch_matches_fixed_width(tiny, lanes):
    """``lanes`` prompts of different lengths prefill together on 6 slots
    (buckets 1, 2, 4, 6): each launch is the smallest bucket that holds the
    prompts it advances, outputs are those of the fixed [max_slots, C]
    launch, and so are kept first-token logits, to a few float32 ulps (the
    CPU backend's matmul blocks its rows by the launch's height)."""
    cfg, m, params = tiny
    rng = np.random.default_rng(17)
    prompts = [list(rng.integers(0, cfg.vocab, 5 + 7 * i))
               for i in range(lanes)]

    def serve(fixed):
        eng = Engine(m, params, MemoryAccountant(m_total=512e6),
                     max_slots=6, s_max=64, kv_backend="ref",
                     prefill_chunk_tokens=8)
        if fixed:
            eng._chunk_buckets = [eng.max_slots]
        for i, p in enumerate(prompts):
            eng.submit(Request(req_id=i, tokens=list(p), max_new=5,
                               logits=[]))
        return eng, {r.req_id: r for r in eng.drain()}

    _, ref = serve(fixed=True)
    log = _ChunkSpans()
    telemetry.enable_spans(log)
    try:
        eng, got = serve(fixed=False)
    finally:
        telemetry.enable_spans(None)
    assert {k: r.out for k, r in got.items()} == \
        {k: r.out for k, r in ref.items()}
    for k, r in got.items():
        want = np.asarray(ref[k].logits[0])
        np.testing.assert_allclose(
            np.asarray(r.logits[0]), want, rtol=0,
            atol=64 * np.finfo(np.float32).eps * np.abs(want).max())
    assert max(a["lanes"] for a in log.args) == lanes
    for a in log.args:
        assert a["width"] == min(b for b in (1, 2, 4, 6) if b >= a["lanes"])
    assert eng.stat_chunk_launches == len(log.args)
    assert eng.stat_chunk_lanes == sum(a["width"] for a in log.args)
    assert eng.arena.check_mirror()


def test_padding_lanes_write_only_the_null_row(tiny):
    """Three prompts advance in a 4-lane launch on 6 slots (and the first
    launch readies every bucket on lanes that are all padding): every row
    that no sequence holds keeps its bytes."""
    cfg, m, params = tiny
    rng = np.random.default_rng(19)
    eng = Engine(m, params, MemoryAccountant(m_total=512e6), max_slots=6,
                 s_max=64, kv_backend="ref", prefill_chunk_tokens=8,
                 arena=KVArena(16, init_rows=64))
    plane = eng.binding.plane
    noise = jax.random.normal(jax.random.PRNGKey(1), plane.k.shape)
    plane.k = noise.astype(plane.k.dtype)
    plane.v = (-noise).astype(plane.v.dtype)
    k0, v0 = np.asarray(plane.k), np.asarray(plane.v)
    for i in range(3):
        eng.submit(Request(req_id=i, max_new=4,
                           tokens=list(rng.integers(0, cfg.vocab, 12 + i))))
    eng.step()                      # admission and one chunk launch
    assert eng.stat_chunk_lanes == 4 and eng.stat_chunk_tokens == 3 * 8
    held = {r for rid in eng.active for r in eng.binding.seq_rows(rid)}
    free = [r for r in range(1, plane.n_rows) if r not in held]
    np.testing.assert_array_equal(np.asarray(plane.k)[:, free], k0[:, free])
    np.testing.assert_array_equal(np.asarray(plane.v)[:, free], v0[:, free])
    assert eng.arena.check_mirror()


def test_no_compile_after_first_chunk_launch(tiny):
    """The first chunk launch readies every bucket: serving prompts that
    advance 2 to 6 together afterwards compiles and loads nothing (a plane
    that never grows)."""
    cfg, m, params = tiny
    rng = np.random.default_rng(13)
    eng = Engine(m, params, MemoryAccountant(m_total=512e6), max_slots=6,
                 s_max=64, kv_backend="ref", prefill_chunk_tokens=4,
                 arena=KVArena(16, init_rows=128))
    eng.submit(Request(req_id=0, tokens=list(rng.integers(0, cfg.vocab, 9)),
                       max_new=4))
    eng.drain()                     # one lane: launches width 1 only
    seen = []

    def count(event, secs, **kw):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        for lanes in (2, 3, 6):
            for i in range(lanes):
                eng.submit(Request(
                    req_id=10 * lanes + i, max_new=4,
                    tokens=list(rng.integers(0, cfg.vocab, 6 + 5 * i))))
            eng.drain()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert eng.stat_chunk_lanes > eng.stat_chunk_launches   # wider launches
    assert seen == []


# --------------------------------------------------------- token budget
def test_max_batch_tokens_defers_chunks_but_never_starves(tiny):
    cfg, m, params = tiny
    prompts = _prompts(cfg)
    _, mono = _drain_all(m, params, prompts, chunk=0)
    # budget of 8 tokens with chunk=8: at most one chunk advances per
    # iteration once decode slots are occupied, yet everything completes
    eng, chk = _drain_all(m, params, prompts, chunk=8, max_batch_tokens=8)
    assert {k: r.out for k, r in chk.items()} == \
           {k: r.out for k, r in mono.items()}
    assert eng.arena.mapped_pages() == 0


# ------------------------------------------------------------- satellites
def test_waiting_is_deque_and_requeue_preserves_order(tiny):
    """release_kv() must requeue evicted actives AHEAD of already-waiting
    requests in their original order (the old ``waiting[:0] = evicted``
    list semantics, now via deque.extendleft)."""
    from collections import deque
    cfg, m, params = tiny
    eng = Engine(m, params, MemoryAccountant(m_total=512e6), max_slots=2,
                 s_max=64, kv_backend="ref")
    assert isinstance(eng.waiting, deque)
    for i in range(4):
        eng.submit(Request(req_id=i, tokens=[1, 2, 3], max_new=8))
    eng.step()                        # admits 0 and 1; 2 and 3 wait
    assert set(eng.active) == {0, 1}
    eng.release_kv()                  # boundary-evict both actives
    assert [r.req_id for r in eng.waiting] == [0, 1, 2, 3]
    # cancel from the middle of the deque still works
    assert eng.cancel(2).req_id == 2
    assert [r.req_id for r in eng.waiting] == [0, 1, 3]


def test_drain_raises_typed_stall_error(tiny):
    cfg, m, params = tiny
    eng = Engine(m, params, MemoryAccountant(m_total=512e6), max_slots=2,
                 s_max=64, kv_backend="ref")
    eng.submit(Request(req_id=0, tokens=[1, 2, 3], max_new=50))
    with pytest.raises(EngineStalledError):
        eng.drain(max_steps=3)        # 50 tokens cannot finish in 3 steps
    # the engine is still consistent: a real drain completes afterwards
    done = eng.drain()
    assert len(done) == 1 and len(done[0].out) == 50


def test_step_returns_only_newly_finished(tiny):
    cfg, m, params = tiny
    eng = Engine(m, params, MemoryAccountant(m_total=512e6), max_slots=2,
                 s_max=64, kv_backend="ref")
    eng.submit(Request(req_id=0, tokens=[1, 2, 3], max_new=2))
    eng.submit(Request(req_id=1, tokens=[4, 5, 6], max_new=6))
    first = eng.step()                # req 0 finishes (prefill + 1 decode)
    assert [r.req_id for r in first] == [0]
    mid = eng.step()                  # req 1 still decoding
    assert mid == []
    while eng.active or eng.waiting:
        last = eng.step()
    assert [r.req_id for r in last] == [1]
    # the accumulated history stays on .finished for wholesale drainers
    assert [r.req_id for r in eng.finished] == [0, 1]


def test_evict_mid_chunked_prefill_frees_partial_pages(tiny):
    cfg, m, params = tiny
    rng = np.random.default_rng(3)
    acc = MemoryAccountant(m_total=512e6)
    eng = Engine(m, params, acc, max_slots=2, s_max=64, kv_backend="ref",
                 prefill_chunk_tokens=4)
    eng.submit(Request(req_id=0, tokens=list(rng.integers(0, cfg.vocab, 40)),
                       max_new=6))
    eng.step()                        # first chunk written, prefill ongoing
    assert eng._prefill_pos.get(0) == 4
    assert eng.arena.mapped_pages() > 0
    req = eng.evict(0)
    assert req is not None and req.out == []
    assert eng._prefill_pos == {}     # streaming cursor dropped
    assert eng.arena.mapped_pages() == 0 and eng.arena.mapped_rows() == 0
    assert acc.m_kv == pytest.approx(0.0)
    assert eng.arena.check_mirror()
