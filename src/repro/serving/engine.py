"""Continuous-batching inference engine (the vLLM-role substrate).

Iteration-level scheduling: each ``step()`` admits waiting requests into free
slots (admission is prediction-guided through the Maestro accountant + rho
margin — Eq. 3's R_need gates admission exactly as §III.C describes), then
assembles ONE fused iteration of at most ``max_batch_tokens``: every active
decode sequence contributes its single next-token position, and sequences
still prefilling contribute one fixed-width chunk of ``prefill_chunk_tokens``
prompt tokens each, streamed into the arena page-by-page through
``Model.prefill_chunk``. Prompts therefore never stall decode slots, slots
join and leave at iteration granularity, and the fixed chunk shape means one
traced executable serves every prompt length (no per-length recompiles).
With ``prefill_chunk_tokens=0`` (the default) admission falls back to the
original monolithic one-shot prefill, bit-identical to earlier revisions.
Preemption is boundary-only: requests are only evicted between engine steps,
with their KV accounted and reclaimable.

KV layout: self-attention K/V lives in the node's PHYSICAL paged arena
(:mod:`repro.serving.kv_arena`) — every pool page grant maps to one arena
row, colocated engines on a node share one store, and decode attends through
per-sequence block tables via the Pallas ``paged_attention`` kernel (the
``kernels.ref`` jnp oracle is the CPU fallback, selected once at engine
construction). What stays per-engine is the small dense *state* cache (SSM
state/conv + static cross-attn K/V), which is registered with the accountant
and dropped on sleep/offload. Models with no self-attention KV (pure SSM)
run the dense decode path; their pool grants remain accounting-only.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.runtime.accounting import MemoryAccountant
from repro.core.runtime.kv_pool import VirtualKVPool
from repro.core.sched.margins import RhoEstimator
from repro.kernels import chunk_prefill as _cp
from repro.kernels import paged_attention as _pa
from repro.kernels import ref as _ref
from repro.models.transformer import Model
from repro.serving.kv_arena import KVArena


def _model_jit(model: "Model", key: tuple, builder):
    """Per-``Model`` cache of the engine's jitted callables.

    Engines are ephemeral — activation churn (sleep/wake under Alg. 2),
    per-policy fleet rebuilds and multi-node zoos construct them by the
    dozen against the same handful of shared ``Model`` objects. A fresh
    ``jax.jit`` wrapper per engine forfeits the XLA compile cache, so a
    10-model fleet recompiled identical programs on every activation;
    keying the wrapper on the model (plus everything the traced program
    closes over: kernel backend, page size) makes compilation once-per-
    program for the model's whole lifetime."""
    cache = getattr(model, "_engine_jit_cache", None)
    if cache is None:
        cache = model._engine_jit_cache = {}
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = builder()
    return fn


class PromptTooLongError(ValueError):
    """Prompt cannot fit the engine's sequence window (needs <= s_max - 1
    tokens so at least one decode position remains). Raised at ``submit``
    time — silent KV overflow is never possible."""


class EngineStalledError(RuntimeError):
    """``drain()`` exhausted its step budget with work still queued or
    active — the engine made no terminal progress (e.g. a waiting request
    whose reservation can never be granted). Raised instead of silently
    returning a partial result set."""


@dataclasses.dataclass
class Request:
    req_id: int
    tokens: List[int]
    max_new: int = 64
    pred_len: Optional[float] = None      # L_hat from the dispatch gateway
    extras: Optional[Dict[str, Any]] = None
    out: List[int] = dataclasses.field(default_factory=list)
    eos: Optional[int] = None
    truncated: bool = False               # finished early (KV exhausted)
    prefill_avoided: int = 0              # prompt tokens served from cache
    submit_s: float = 0.0                 # wall stamp at engine submit
    ttft_s: float = 0.0                   # wall submit -> first kept token
    # None keeps no logits; a list collects, for each token the host picks
    # (prefill, chunk or one-token decode, not the on-device horizon), that
    # token's f32 logits row [Vp], left on the device — for correctness
    # checks against a reference
    logits: Optional[List[Any]] = None


class Engine:
    def __init__(self, model: Model, params, accountant: MemoryAccountant,
                 max_slots: int = 4, s_max: int = 256,
                 page_tokens: int = 16, arena: Optional[KVArena] = None,
                 kv_backend: Optional[str] = None, prefix_cache=None,
                 prefix_ns: Optional[str] = None,
                 max_batch_tokens: Optional[int] = None,
                 prefill_chunk_tokens: int = 0,
                 decode_horizon: int = 1):
        """``arena``: the node-shared physical page store (a private one is
        created for standalone engines). ``kv_backend``: "pallas" | "ref" |
        "dense" — default picks the Pallas paged kernel on TPU and the jnp
        reference elsewhere; models without self-attention KV always run
        "dense" (state-only). ``prefix_cache``: None/False (off, the
        default — disabled runs stay bit-identical), True, or a
        :class:`~repro.serving.prefix_cache.PrefixCacheConfig`; only takes
        effect on paged engines whose model supports prefix reuse.
        ``prefix_ns``: digest namespace for the prefix index — the fleet
        passes the SERVING model name here so gateway-side request digests
        (computed from the same name) match the node's advertised index;
        defaults to the model config name for standalone engines.
        ``prefill_chunk_tokens``: > 0 switches prefill to fixed-width
        chunks fused into the decode iteration (paged engines whose model
        supports chunked prefill only; others keep monolithic prefill).
        ``max_batch_tokens``: per-iteration token budget across decode
        positions + prefill chunks (None = unbounded; at least one chunk
        always advances so prefill cannot starve).
        ``decode_horizon``: > 1 fuses up to that many decode iterations into
        one jitted on-device program per ``step()`` (paged engines whose
        model supports it only; see :meth:`Model.decode_horizon`) — one host
        sync per horizon instead of per token. 1 (the default) keeps the
        original one-token step, bit-identical to earlier revisions; mixed
        prefill+decode iterations always fall back to one-token decode so
        chunked-prefill fusion semantics are untouched."""
        self.model = model
        self.params = params
        self.acc = accountant
        self.s_max = s_max
        self.max_slots = max_slots
        self.arena = arena if arena is not None else KVArena(page_tokens)
        self.page_tokens = self.arena.page_tokens
        alpha = max(model.cfg.kv_bytes_per_token(
            dtype_bytes=jnp.dtype(model.cfg.dtype).itemsize), 1)
        self.alpha = alpha
        self.pool = VirtualKVPool(accountant,
                                  page_bytes=alpha * self.page_tokens,
                                  page_tokens=self.page_tokens)
        self.pool.set_virtual_budget(model.cfg.name,
                                     alpha * s_max * max_slots * 4)
        bases, n_layers, Hkv, hd, kv_dtype = model.paged_kv_layout()
        if kv_backend is None:
            kv_backend = "pallas" if jax.default_backend() == "tpu" else "ref"
        if n_layers == 0:
            kv_backend = "dense"          # nothing to page: state-only model
        assert kv_backend in ("pallas", "ref", "dense"), kv_backend
        self.kv_backend = kv_backend
        self.paged = kv_backend != "dense"
        self._kv_bases = bases
        self._kv_slots = sorted(bases, key=bases.get)
        self.binding = self.arena.register(
            model.cfg.name, self.pool, s_max=s_max,
            n_layers=n_layers if self.paged else 0,
            n_kv_heads=Hkv, head_dim=hd, dtype=kv_dtype)
        self._pc = None
        self._pc_ns = prefix_ns or model.cfg.name
        if prefix_cache:
            from repro.serving.prefix_cache import PrefixCacheConfig
            pc_cfg = (prefix_cache if isinstance(prefix_cache,
                                                 PrefixCacheConfig)
                      else PrefixCacheConfig())
            if pc_cfg.enabled and self.paged and model.supports_prefix_reuse:
                self._pc = self.arena.enable_prefix_cache(accountant, pc_cfg)
        self._hits: Dict[int, Any] = {}
        self.rho = RhoEstimator()
        self.waiting: Deque[Request] = collections.deque()
        self.active: Dict[int, Request] = {}
        self.slot_of: Dict[int, int] = {}
        self.free_slots = list(range(max_slots))
        self.positions = np.zeros(max_slots, np.int32)
        self._needs: Dict[int, float] = {}   # admitted R_need, by req_id
        self._state_key = f"{model.cfg.name}::decode-state"
        self._state_bytes = 0
        self.cache = None
        self._ensure_cache()
        self.horizon = 1
        if self.paged:
            attend = (functools.partial(_pa.paged_attention,
                                        page_size=self.page_tokens)
                      if kv_backend == "pallas"
                      else _ref.paged_attention_ref)
            self._decode = _model_jit(
                model, ("decode_paged", kv_backend, self.page_tokens),
                lambda: jax.jit(
                    functools.partial(model.decode_step_paged,
                                      attend=attend),
                    donate_argnums=(1, 2, 3)))
            if decode_horizon and int(decode_horizon) > 1 \
                    and model.supports_decode_horizon:
                self.horizon = int(decode_horizon)
                self._horizon_fwd = _model_jit(
                    model, ("decode_horizon", kv_backend, self.page_tokens,
                            self.horizon),
                    lambda: jax.jit(
                        functools.partial(model.decode_horizon,
                                          attend=attend,
                                          horizon=self.horizon,
                                          page_tokens=self.page_tokens),
                        donate_argnums=(1, 2, 3)))
        else:
            self._decode = _model_jit(
                model, ("decode_dense",),
                lambda: jax.jit(model.decode_step, donate_argnums=(1,)))
        # persistent device-side decode tables (horizon > 1 only): block
        # tables / positions are uploaded when admission, release, eviction
        # or page growth dirties them — never rebuilt per token
        self._dev_bt = None
        self._dev_pos = None
        self._tables_dirty = True

        def _prefill_tok(p, toks, extras):
            # first-token argmax folded into the jitted prefill: the host
            # fetches one int32 per sequence, never a logits row
            logits, cache = model.prefill(p, toks, extras)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), logits,
                    cache)

        self._prefill_fwd = _model_jit(model, ("prefill_tok",),
                                       lambda: jax.jit(_prefill_tok))
        self.max_batch_tokens = max_batch_tokens
        self.chunk_tokens = (int(prefill_chunk_tokens)
                             if (prefill_chunk_tokens and self.paged
                                 and model.supports_chunked_prefill) else 0)
        if self.chunk_tokens:
            attend_c = (functools.partial(_cp.chunk_prefill_attention,
                                          page_size=self.page_tokens)
                        if kv_backend == "pallas"
                        else _ref.chunk_prefill_attention_ref)

            def _chunk_tok(p, kp, vp, toks, pos, bt, rows, offs, last_idx):
                logits, kp, vp = model.prefill_chunk(
                    p, kp, vp, toks, pos, bt, rows, offs, last_idx,
                    attend=attend_c)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        logits, kp, vp)

            self._chunk_fwd = _model_jit(
                model, ("chunk_tok", kv_backend, self.page_tokens),
                lambda: jax.jit(_chunk_tok, donate_argnums=(1, 2)))
        self._prefill_pos: Dict[int, int] = {}   # rid -> prompt tokens done
        # stubbed modality frontends (§IV prototype): encoder-decoder and
        # cross-attention models prefill against precomputed frame / patch
        # embeddings. A request that arrives without them (the text-only
        # gateway plane) gets this engine-constant deterministic stub, so
        # every family of the zoo — whisper and vision included — can be
        # activated and served without shipping modality tensors over the
        # worker transport.
        self._modal_extras = self._make_modal_extras()
        # iteration telemetry: distinct prefill forward shapes (the honest
        # compile-count proxy — jit retraces exactly per new signature),
        # prefill/decode token split, and fused-iteration counts
        self._prefill_shapes: set = set()
        self.prefill_compiles = 0
        self.stat_prefill_tokens = 0
        self.stat_decode_tokens = 0
        self.stat_steps = 0
        self.stat_fused_steps = 0
        # decode-horizon telemetry: horizon launches and decode-side host
        # syncs (one blocking device->host fetch per one-token decode batch
        # OR per horizon launch) — host_syncs_per_token = syncs / tokens
        self.stat_horizon_steps = 0
        self.stat_decode_syncs = 0
        self.finished: List[Request] = []

    # -------------------------------------------------------------- state
    def _ensure_cache(self) -> None:
        """(Re)allocate the dense per-slot cache — SSM state / conv + static
        cross K/V on the paged path, the full dense KV cache on the dense
        fallback — and register its bytes with the accountant so engine
        state is never silently device-resident."""
        if self.cache is not None:
            return
        specs_fn = (self.model.state_cache_specs if self.paged
                    else self.model.cache_specs)
        structs, _ = specs_fn(self.max_slots, self.s_max)
        self.cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  structs)
        nbytes = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                     for s in jax.tree.leaves(structs))
        self._state_bytes = nbytes
        if nbytes:
            self.acc.register_context(self._state_key, nbytes)

    def release_kv(self) -> None:
        """Drop every byte of device KV this engine holds: boundary-evict
        active requests back to the front of the waiting queue (their arena
        pages return to pool + plane), then free the dense state cache and
        its accountant registration. Called on sleep/offload — a slept model
        must actually return its memory."""
        evicted = [req for rid in list(self.active)
                   if (req := self.evict(rid)) is not None]
        # requeue ahead of the waiting queue, original order kept
        self.waiting.extendleft(reversed(evicted))
        self.binding.release_all()
        if self._pc is not None:       # slept models give back their pins
            self._pc.flush_model(self._pc_ns)
        if self.cache is not None:
            self.cache = None
            if self._state_bytes:
                self.acc.unregister_context(self._state_key)
            self._state_bytes = 0
        self._dev_bt = self._dev_pos = None     # device tables go with KV
        self._tables_dirty = True

    # ------------------------------------------------------------- admission
    def submit(self, req: Request) -> None:
        if len(req.tokens) > self.s_max - 1:
            raise PromptTooLongError(
                f"prompt of {len(req.tokens)} tokens exceeds the engine "
                f"window (s_max={self.s_max}, >=1 decode slot required)")
        if not req.submit_s:
            req.submit_s = time.perf_counter()
        self.waiting.append(req)

    def _r_need(self, req: Request) -> float:
        pred = req.pred_len if req.pred_len is not None else req.max_new
        return self.rho.r_need(self.alpha * (len(req.tokens) + pred))

    def _admit(self) -> List[Request]:
        admitted = []
        while self.waiting and self.free_slots:
            req = self.waiting[0]
            need = self._r_need(req)
            # pages must cover prompt + the first decode write, but never
            # exceed the sequence window (KV past s_max is unusable, and
            # block tables are sized for exactly ceil(s_max/page) pages)
            need_tokens = min(max(int(need / self.alpha),
                                  len(req.tokens) + 1), self.s_max)
            hit = alias = None
            if self._pc is not None:
                hit = self._prefix_lookup(req)
                alias = list(hit.rows)
                if hit.partial_row is not None:
                    alias.append(hit.partial_row)
                alias = alias or None
            if not self.binding.alloc_seq(req.req_id, self.model.cfg.name,
                                          need_tokens, alias_rows=alias):
                break   # memory-infeasible: reject-for-now (backpressure)
            if hit is not None:
                if hit.partial_row is not None:
                    # the divergent tail lands mid-page: privatise that page
                    # (copy-on-write) before suffix prefill overwrites it —
                    # the index pin guarantees the row is shared, so this
                    # always copies
                    if self.binding.make_private(req.req_id, len(hit.rows)):
                        self._pc.cow_copies += 1
                self._hits[req.req_id] = hit
            self.waiting.popleft()
            slot = self.free_slots.pop()
            self.slot_of[req.req_id] = slot
            self.active[req.req_id] = req
            self._needs[req.req_id] = need
            self._tables_dirty = True
            admitted.append(req)
        return admitted

    def _prefix_lookup(self, req: Request):
        """Match the prompt against the node prefix index, capped so the
        final prompt token always runs through prefill (its logit seeds
        decoding)."""
        from repro.serving.prefix_cache import page_digests
        name = self._pc_ns
        digs = page_digests(req.tokens, self.page_tokens, name)
        m = self._pc.match(name, digs, req.tokens, self.page_tokens)
        P = len(req.tokens)
        if m.n_full_tokens >= P:          # whole prompt cached: keep 1 page
            m.rows.pop()
            m.n_full_tokens -= self.page_tokens
            m.partial_row, m.partial_overlap = None, 0
        if m.partial_row is not None:
            m.partial_overlap = min(m.partial_overlap,
                                    P - 1 - m.n_full_tokens)
            if m.partial_overlap <= 0:
                m.partial_row, m.partial_overlap = None, 0
        m.digests = digs
        return m

    # -------------------------------------------------------------- prefill
    def _note_prefill_shape(self, sig) -> None:
        """Count distinct prefill forward signatures — the compile-count
        telemetry. jit retraces exactly once per new signature, so this is
        the honest recompile proxy without reaching into jit internals."""
        if sig not in self._prefill_shapes:
            self._prefill_shapes.add(sig)
            self.prefill_compiles += 1

    @staticmethod
    def _keep_logits(req: Request, logits, row: int) -> None:
        if req.logits is not None:
            req.logits.append(logits[row])

    def _first_token(self, req: Request, tok: int) -> None:
        req.out.append(tok)
        if not req.ttft_s and req.submit_s:
            req.ttft_s = time.perf_counter() - req.submit_s

    def _prefill(self, req: Request) -> None:
        self._ensure_cache()
        slot = self.slot_of[req.req_id]
        hit = self._hits.pop(req.req_id, None)
        if hit is not None and hit.tokens_matched > 0:
            self._prefill_suffix(req, hit, slot)
        else:
            self._prefill_full(req, slot)
        if self._pc is not None:
            digs = (hit.digests if hit is not None else None)
            self._index_prompt(req, digs)

    def _begin_chunked(self, req: Request) -> None:
        """Register a newly admitted request with the chunked-prefill plan:
        its prompt streams into the arena ``chunk_tokens`` at a time across
        the next iterations (cache-hit prefixes are skipped — the matched
        pages are already aliased into this sequence's block table, so the
        first chunk starts right after them)."""
        hit = self._hits.get(req.req_id)
        p0 = hit.tokens_matched if hit is not None else 0
        self._prefill_pos[req.req_id] = p0
        if p0:
            req.prefill_avoided = p0
            self._pc.tokens_avoided += p0

    def _make_modal_extras(self) -> Optional[Dict[str, Any]]:
        """Deterministic stub inputs for the model's modality frontend
        (None for text-only models): whisper-style frames [1,F,D] or VLM
        patch embeddings [1,N,C], seeded once per engine so repeated runs
        are bit-identical."""
        cfg = self.model.cfg
        key = jax.random.PRNGKey(0)
        if cfg.encoder is not None:
            return {"frames": jax.random.normal(
                key, (1, cfg.encoder.n_frames, cfg.d_model), cfg.dtype)}
        if cfg.cross_attn is not None and cfg.family == "vlm":
            cd = cfg.cross_attn.ctx_dim or cfg.d_model
            return {"ctx_embeds": jax.random.normal(
                key, (1, cfg.cross_attn.n_ctx_tokens, cd), cfg.dtype)}
        return None

    def _prefill_full(self, req: Request, slot: int) -> None:
        toks = jnp.asarray(req.tokens, jnp.int32)[None, :]
        first_tok, logits, cache = self._prefill_fwd(
            self.params, toks, req.extras or self._modal_extras or {})
        self._keep_logits(req, logits, 0)
        P = len(req.tokens)
        self._note_prefill_shape(("full", P))
        self.stat_prefill_tokens += P
        if self.paged:
            # [G,1,P,Hkv,hd] per slot -> layer-stacked [L,P,Hkv,hd] in
            # plane layout order (slot base + group)
            k_all = jnp.concatenate(
                [cache[s]["k"][:, 0] for s in self._kv_slots], axis=0)
            v_all = jnp.concatenate(
                [cache[s]["v"][:, 0] for s in self._kv_slots], axis=0)
            self.binding.write_prompt(req.req_id, k_all, v_all)

        def write(dst, src):
            # dst [G, max_slots, S_max, ...]; src [G, 1, P, ...]
            if dst.shape[2] == src.shape[2]:      # static cross entries
                return dst.at[:, slot].set(src[:, 0])
            return dst.at[:, slot, :P].set(src[:, 0])

        def write_state(dst, src):                 # ssm state/conv
            return dst.at[:, slot].set(src[:, 0])

        for name, entry in cache.items():
            if self.paged and name in self._kv_bases:
                continue                           # lives in the arena
            for kname, arr in entry.items():
                tgt = self.cache[name][kname]
                if kname in ("k", "v"):
                    self.cache[name][kname] = write(tgt, arr)
                else:
                    self.cache[name][kname] = write_state(tgt, arr)
        self.positions[slot] = P
        self._tables_dirty = True
        self._first_token(req, int(first_tok[0]))

    def _prefill_suffix(self, req: Request, hit, slot: int) -> None:
        """Cache-hit prefill: gather matched prefix KV from the arena rows
        this sequence aliases, run the forward only over the unmatched
        suffix, and scatter the suffix KV behind the prefix."""
        M = hit.tokens_matched
        plane = self.binding.plane
        L = self.binding.n_layers
        page = self.page_tokens
        n_pages = -(-M // page)
        idx = jnp.asarray(self.binding.seq_rows(req.req_id)[:n_pages],
                          jnp.int32)
        tail = plane.k.shape[3:]
        pk = plane.k[:L, idx].reshape((L, n_pages * page) + tail)[:, :M]
        pv = plane.v[:L, idx].reshape((L, n_pages * page) + tail)[:, :M]
        toks = jnp.asarray(req.tokens[M:], jnp.int32)[None, :]
        self._note_prefill_shape(("suffix", len(req.tokens) - M, M))
        self.stat_prefill_tokens += len(req.tokens) - M
        model = self.model

        def _suffix_tok(p, toks, pk, pv):
            logits, k_sfx, v_sfx = model.prefill_suffix(p, toks, pk, pv)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), logits,
                    k_sfx, v_sfx)

        first_tok, logits, k_sfx, v_sfx = _model_jit(
            self.model, ("prefill_suffix_tok",),
            lambda: jax.jit(_suffix_tok))(
            self.params, toks, pk, pv)
        self._keep_logits(req, logits, 0)
        self.binding.write_prompt_at(req.req_id, k_sfx[:, 0], v_sfx[:, 0], M)
        self.positions[slot] = len(req.tokens)
        self._tables_dirty = True
        self._first_token(req, int(first_tok[0]))
        req.prefill_avoided = M
        self._pc.tokens_avoided += M

    def _index_prompt(self, req: Request, digs=None) -> None:
        """Publish every full prompt page into the prefix index (pinning its
        row) so successor stages sharing this prefix can alias it."""
        from repro.serving.prefix_cache import page_digests, root_key
        name = self._pc_ns
        page = self.page_tokens
        if digs is None:
            digs = page_digests(req.tokens, page, name)
        rows = self.binding.seq_rows(req.req_id)
        parent = root_key(name)
        for i, d in enumerate(digs):
            self._pc.insert(name, d, parent, self.binding.plane, rows[i],
                            req.tokens[i * page:(i + 1) * page],
                            n_prefix_tokens=(i + 1) * page)
            parent = d

    def _prefill_chunk_batch(self, rids: List[int]) -> None:
        """One fused chunk forward for the given mid-prefill sequences: each
        contributes the next ``chunk_tokens`` of its prompt at fixed shape
        [max_slots, C]. Slots not advancing this iteration (idle, decoding,
        or budget-deferred) are padding — their tokens/positions are zero and
        their write coordinates point at the plane's null row, so the forward
        is shape-stable and their garbage rows are discarded. A sequence
        whose chunk reaches the end of its prompt gets its first output
        token from that chunk's last-row logits and joins decode at the NEXT
        iteration (join-at-iteration-granularity)."""
        self._ensure_cache()
        C = self.chunk_tokens
        page = self.page_tokens
        toks = np.zeros((self.max_slots, C), np.int32)
        pos = np.zeros((self.max_slots, C), np.int32)
        rows = np.zeros((self.max_slots, C), np.int32)
        offs = np.zeros((self.max_slots, C), np.int32)
        bt = np.zeros((self.max_slots, self.binding.bt_width), np.int32)
        last_idx = np.zeros(self.max_slots, np.int32)
        for rid in rids:
            req = self.active[rid]
            slot = self.slot_of[rid]
            p0 = self._prefill_pos[rid]
            n = min(C, len(req.tokens) - p0)
            table = self.binding.row_table(rid)
            bt[slot] = table
            abs_t = np.arange(p0, p0 + n)
            toks[slot, :n] = req.tokens[p0:p0 + n]
            pos[slot, :n] = abs_t
            rows[slot, :n] = table[abs_t // page]
            offs[slot, :n] = abs_t % page
            last_idx[slot] = n - 1
            self._prefill_pos[rid] = p0 + n
            self.stat_prefill_tokens += n
        self._note_prefill_shape(("chunk", C))
        self._tables_dirty = True
        plane = self.binding.plane
        tok_dev, logits, plane.k, plane.v = self._chunk_fwd(
            self.params, plane.k, plane.v, jnp.asarray(toks),
            jnp.asarray(pos), jnp.asarray(bt), jnp.asarray(rows),
            jnp.asarray(offs), jnp.asarray(last_idx))
        nxt = np.asarray(tok_dev)
        for rid in rids:
            req = self.active[rid]
            if self._prefill_pos[rid] < len(req.tokens):
                continue                       # more chunks to stream
            del self._prefill_pos[rid]
            slot = self.slot_of[rid]
            self.positions[slot] = len(req.tokens)
            self._keep_logits(req, logits, slot)
            self._first_token(req, int(nxt[slot]))
            if self._pc is not None:
                hit = self._hits.pop(rid, None)
                self._index_prompt(req, hit.digests if hit is not None
                                   else None)

    # --------------------------------------------------------------- decode
    def step(self) -> List[Request]:
        """One fused engine iteration; returns the requests that finished
        DURING THIS CALL only (the accumulated history stays on
        ``self.finished`` for owners that drain it wholesale)."""
        n0 = len(self.finished)
        self.stat_steps += 1
        for req in self._admit():
            if self.chunk_tokens:
                self._begin_chunked(req)
            else:
                self._prefill(req)
        # sequences still streaming their prompt join decode at the NEXT
        # iteration after their final chunk — snapshot the decode set first
        decode_rids = [rid for rid in self.active
                       if rid not in self._prefill_pos]
        # mixed prefill+decode iterations fall back to one-token decode so
        # chunked-prefill fusion semantics stay untouched; pure-decode
        # iterations launch the on-device horizon
        use_horizon = self.horizon > 1 and not self._prefill_pos
        caps: Dict[int, int] = {}
        if decode_rids and self.paged:
            # grow page coverage for this step's token writes; a sequence
            # the pool cannot extend finishes truncated (honest
            # backpressure instead of silent overflow)
            for rid in list(decode_rids):
                pos = int(self.positions[self.slot_of[rid]])
                if use_horizon:
                    # pre-grant up to a horizon's worth of pages; a partial
                    # grant caps that lane's emission budget (it stays
                    # active and retries next step), a zero grant truncates
                    # exactly like the one-token path
                    req = self.active[rid]
                    want = min(self.horizon, req.max_new - len(req.out),
                               self.s_max - 1 - pos)
                    got = self._pregrant(rid, pos, want)
                    if got > 0:
                        caps[rid] = got
                        continue
                elif self.binding.ensure_tokens(rid, pos + 1):
                    continue
                self.active[rid].truncated = True
                self._release(rid)
                decode_rids.remove(rid)
        if self._prefill_pos:
            # token-budget split: decode contributes one position per
            # sequence, the remainder admits whole prefill chunks; at least
            # one chunk always advances (prefill cannot starve)
            if self.max_batch_tokens is None:
                n_adv = len(self._prefill_pos)
            else:
                room = self.max_batch_tokens - len(decode_rids)
                n_adv = max(room // self.chunk_tokens, 1)
            advance = list(self._prefill_pos)[:n_adv]
            self._prefill_chunk_batch(advance)
            if decode_rids:
                self.stat_fused_steps += 1
        if decode_rids and use_horizon and self.paged:
            self._decode_horizon_batch(decode_rids, caps)
        elif decode_rids:
            self._ensure_cache()
            toks = np.zeros((self.max_slots, 1), np.int32)
            for rid in decode_rids:
                toks[self.slot_of[rid], 0] = self.active[rid].out[-1]
            if self.paged:
                logits = self._decode_paged(toks, decode_rids)
            else:
                logits, self.cache = self._decode(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(self.positions))
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            self.stat_decode_syncs += 1
            self.stat_decode_tokens += len(decode_rids)
            self._tables_dirty = True
            done = []
            for rid in decode_rids:
                req = self.active[rid]
                slot = self.slot_of[rid]
                tok = int(nxt[slot])
                self._keep_logits(req, logits, slot)
                req.out.append(tok)
                self.positions[slot] += 1
                if (len(req.out) >= req.max_new
                        or (req.eos is not None and tok == req.eos)
                        or self.positions[slot] >= self.s_max - 1):
                    done.append(rid)
            for rid in done:
                self._release(rid)
        return self.finished[n0:]

    def _pregrant(self, rid: int, pos: int, want: int) -> int:
        """Pre-grant pages for up to ``want`` horizon writes starting at
        ``pos``. Returns the emission budget actually covered (0 = not even
        one write grantable -> caller truncates, the same backpressure as
        the one-token path). Grants are page-granular: when the pool
        refuses the full horizon the budget falls back page by page, down
        to whatever the current grant already covers."""
        page = self.page_tokens
        have = self.binding.token_capacity(rid) - pos
        e = want
        while e > max(have, 0):
            if self.binding.ensure_tokens(rid, pos + e):
                self._tables_dirty = True   # new pages -> new block rows
                break
            # largest budget needing one page fewer
            e = (pos + e - 1) // page * page - pos
        if e <= 0:
            return 0
        # covered by pages already granted: record the token high-water
        # mark with the pool (never allocates here, cannot fail)
        self.binding.ensure_tokens(rid, pos + e)
        if self._pc is not None:
            # a horizon write must never land on a shared row: privatise
            # every page the launch will touch before it starts
            for pidx in range(pos // page, (pos + e - 1) // page + 1):
                if self.binding.make_private(rid, pidx):
                    self._pc.cow_copies += 1
                    self._tables_dirty = True
        return e

    def _decode_horizon_batch(self, decode_rids: List[int],
                              caps: Dict[int, int]) -> None:
        """One on-device horizon launch: up to ``self.horizon`` decode
        iterations for every decoding lane, ONE host sync for the token
        block. Per-lane stop masks freeze finished lanes on device; the
        host re-applies the same done predicate over the emitted tokens to
        release finished requests (boundary preemption granularity becomes
        the horizon launch, measured — not asserted — in
        ``benchmarks/decode_horizon.py``)."""
        self._ensure_cache()
        B = self.max_slots
        live = np.zeros(B, bool)
        last = np.zeros(B, np.int32)
        rem = np.ones(B, np.int32)
        cap = np.zeros(B, np.int32)
        eos = np.full(B, -1, np.int32)
        for rid in decode_rids:
            slot = self.slot_of[rid]
            req = self.active[rid]
            live[slot] = True
            last[slot] = req.out[-1]
            rem[slot] = req.max_new - len(req.out)
            cap[slot] = caps[rid]
            if req.eos is not None:
                eos[slot] = req.eos
        if self._tables_dirty or self._dev_bt is None:
            bt = np.zeros((B, self.binding.bt_width), np.int32)
            for rid in decode_rids:
                bt[self.slot_of[rid]] = self.binding.row_table(rid)
            self._dev_bt = jnp.asarray(bt)
            self._dev_pos = jnp.asarray(self.positions)
            self._tables_dirty = False
        plane = self.binding.plane
        tok_blk, self._dev_pos, self.cache, plane.k, plane.v = \
            self._horizon_fwd(
                self.params, self.cache, plane.k, plane.v, self._dev_bt,
                self._dev_pos, jnp.asarray(last), jnp.asarray(live),
                jnp.asarray(rem), jnp.asarray(cap), jnp.asarray(eos),
                jnp.int32(self.s_max))
        blk = np.asarray(tok_blk)               # the ONE host sync
        self.stat_decode_syncs += 1
        self.stat_horizon_steps += 1
        done = []
        for rid in decode_rids:
            req = self.active[rid]
            slot = self.slot_of[rid]
            for t in blk[slot]:
                if t < 0:
                    break                       # lane froze on device
                tok = int(t)
                req.out.append(tok)
                self.positions[slot] += 1
                self.stat_decode_tokens += 1
                if (len(req.out) >= req.max_new
                        or (req.eos is not None and tok == req.eos)
                        or self.positions[slot] >= self.s_max - 1):
                    done.append(rid)
                    break
        for rid in done:
            self._release(rid)

    def _decode_paged(self, toks: np.ndarray, decode_rids: List[int]):
        """One paged decode step: build block tables / write coordinates for
        the decoding slots and run the arena-backed decode. Idle and
        mid-prefill slots point at the plane's null row (reads and writes
        land there harmlessly)."""
        bt = np.zeros((self.max_slots, self.binding.bt_width), np.int32)
        seq_lens = np.ones(self.max_slots, np.int32)
        rows = np.zeros(self.max_slots, np.int32)
        offs = np.zeros(self.max_slots, np.int32)
        for rid in decode_rids:
            slot = self.slot_of[rid]
            pos = int(self.positions[slot])
            if self._pc is not None and self.binding.make_private(
                    rid, pos // self.page_tokens):
                # defensive: a decode write must never land on a shared row
                self._pc.cow_copies += 1
            table = self.binding.row_table(rid)
            bt[slot] = table
            seq_lens[slot] = pos + 1
            rows[slot] = table[pos // self.page_tokens]
            offs[slot] = pos % self.page_tokens
        plane = self.binding.plane
        logits, self.cache, plane.k, plane.v = self._decode(
            self.params, self.cache, plane.k, plane.v, jnp.asarray(bt),
            jnp.asarray(seq_lens), jnp.asarray(rows), jnp.asarray(offs),
            jnp.asarray(toks), jnp.asarray(self.positions))
        return logits

    def _release(self, rid: int) -> None:
        req = self.active.pop(rid)
        slot = self.slot_of.pop(rid)
        actual = self.alpha * (len(req.tokens) + len(req.out))
        # calibrate against the reservation ADMISSION charged — recomputing
        # r_need here would read a rho already moved by earlier releases
        self.rho.observe(actual, max(self._needs.pop(rid, 1.0), 1.0))
        self.binding.free_seq(rid)      # pages -> pool -> arena rows
        self.free_slots.append(slot)
        self.positions[slot] = 0
        self._tables_dirty = True
        self.finished.append(req)

    # ------------------------------------------------------------ preemption
    def cancel(self, req_id: int) -> Optional[Request]:
        """Withdraw a request still waiting for admission (no KV held)."""
        for i, r in enumerate(self.waiting):
            if r.req_id == req_id:
                del self.waiting[i]
                return r
        return None

    def evict(self, req_id: int) -> Optional[Request]:
        """Boundary preemption: release an active request between engine
        steps. Its KV pages return to the pool, the arena plane and the
        accountant, the slot frees, and the partial output is discarded —
        the caller requeues the stage, which restarts from its prompt
        (§III.D boundary semantics)."""
        req = self.active.pop(req_id, None)
        if req is None:
            return self.cancel(req_id)
        slot = self.slot_of.pop(req_id)
        self._needs.pop(req_id, None)
        self._hits.pop(req_id, None)
        # mid-chunked-prefill eviction: drop the streaming cursor too — the
        # partially-written pages go back with free_seq below, and a later
        # re-admission restarts the prompt from scratch
        self._prefill_pos.pop(req_id, None)
        self.binding.free_seq(req_id)
        self.free_slots.append(slot)
        self.positions[slot] = 0
        self._tables_dirty = True
        req.out.clear()
        if req.logits is not None:
            req.logits.clear()
        req.ttft_s = 0.0            # the discarded first token doesn't count
        return req

    def drain(self, max_steps: int = 10_000) -> List[Request]:
        steps = max_steps
        while (self.waiting or self.active) and steps:
            self.step()
            steps -= 1
        if self.waiting or self.active:
            raise EngineStalledError(
                f"drain({max_steps}) exhausted with {len(self.waiting)} "
                f"waiting / {len(self.active)} active requests still held")
        out, self.finished = self.finished, []
        return out
