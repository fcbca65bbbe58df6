"""Model assembly: heterogeneous layer slots, scan-over-groups execution,
embedding / LM head, chunked cross-entropy, prefill & decode paths, and the
Whisper-style encoder.

Layer heterogeneity (dense / MoE / SSM / hybrid / cross-attn) is expressed as a
repeating *period* of layer slots (``cfg.layer_pattern_period``); parameters of
repeated groups are stacked on a leading "stack" axis and executed with
``lax.scan`` (keeps HLO size O(period), compile time flat in depth, and remat
boundaries exactly at group edges).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ArchConfig, input_specs
from repro.distributed.sharding import ShardingCtx, mesh_rules
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models.common import (Leaf, abstract_tree, init_tree, pad_vocab,
                                 pspec_tree, rms_norm)
from repro.models import flags

SlotKind = Tuple[str, str, bool]  # (mixer, ffn, has_cross)


def slot_kinds(cfg: ArchConfig) -> List[SlotKind]:
    kinds = []
    for i in range(cfg.layer_pattern_period):
        mixer = "attn" if cfg.is_attn_layer(i) else "ssm"
        if cfg.is_moe_layer(i):
            ffn = "moe"
        elif cfg.family == "ssm" or (cfg.family == "hybrid" and mixer == "ssm"):
            ffn = "none"
        else:
            ffn = "dense"
        cross = cfg.is_cross_layer(i) or cfg.family == "encdec"
        kinds.append((mixer, ffn, cross))
    return kinds


def _stack(defs, g: int):
    """Prepend a stacked-group dim to every Leaf."""
    return jax.tree_util.tree_map(
        lambda l: dataclasses.replace(l, shape=(g,) + l.shape,
                                      axes=("stack",) + l.axes),
        defs, is_leaf=lambda x: isinstance(x, Leaf))


class Model:
    """Pure-functional model bound to one ArchConfig (+ optional mesh)."""

    def __init__(self, cfg: ArchConfig, mesh=None, mode: str = "tp_sp"):
        self.cfg = cfg
        self.ctx = ShardingCtx(mesh, mode=mode)
        self.kinds = slot_kinds(cfg)
        self.period = cfg.layer_pattern_period
        assert cfg.n_layers % self.period == 0, (cfg.name, cfg.n_layers, self.period)
        self.n_groups = cfg.n_layers // self.period
        self.vocab_padded = pad_vocab(cfg.vocab, 256)
        self._defs = self._build_defs()

    # ------------------------------------------------------------------ defs
    def _slot_defs(self, kind: SlotKind) -> Dict[str, Any]:
        cfg = self.cfg
        mixer, ffn, cross = kind
        d: Dict[str, Any] = {}
        if mixer == "attn":
            d["attn"] = L.attn_defs(cfg)
        else:
            d["ssm"] = M2.ssm_defs(cfg)
        if cross:
            d["cross"] = L.attn_defs(cfg, cross=True)
        if ffn == "dense":
            d["ffn"] = L.ffn_defs(cfg, gelu=cfg.ffn_gelu)
        elif ffn == "moe":
            d["moe"] = MOE.moe_defs(cfg)
        return d

    def _build_defs(self):
        cfg = self.cfg
        D, dt = cfg.d_model, cfg.dtype
        Vp = self.vocab_padded
        group = {f"slot{i}": self._slot_defs(k) for i, k in enumerate(self.kinds)}
        defs: Dict[str, Any] = {
            "embed": Leaf((Vp, D), ("tp", "fsdp"), dt, scale=1.0),
            "final_ln": Leaf((D,), (None,), dt, init="ones"),
            "groups": _stack(group, self.n_groups),
        }
        if not cfg.tie_embeddings:
            defs["lm_head"] = Leaf((D, Vp), ("fsdp", "tp"), dt)
        if cfg.encoder is not None:
            enc_layer = {
                "attn": L.attn_defs(cfg),
                "ffn": L.ffn_defs(cfg, gelu=True),
            }
            defs["encoder"] = {
                "layers": _stack(enc_layer, cfg.encoder.n_layers),
                "ln": Leaf((D,), (None,), dt, init="ones"),
            }
        return defs

    def param_defs(self):
        return self._defs

    def init(self, key, dtype_override=None):
        return init_tree(self._defs, key, dtype_override)

    def abstract_params(self, dtype_override=None):
        return abstract_tree(self._defs, dtype_override)

    def param_pspecs(self):
        return pspec_tree(self._defs, mesh_rules(self.ctx.mesh))

    # -------------------------------------------------------------- embedding
    def embed(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0)
        if self.ctx.mode == "fsdp_cp" and tokens.shape[1] == 1:
            return self.ctx.cs(x, None, None, "fsdp")  # stationary decode
        return self.ctx.cs(x, "batch", "sp", None)

    def unembed_weight(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    # ---------------------------------------------------------------- encoder
    def encode(self, params, frames):
        """Whisper-style encoder over precomputed frame embeddings [B,F,D]."""
        cfg = self.cfg
        pos = jnp.arange(frames.shape[1])[None, :]

        def body(x, lp):
            o, _ = L.attn_full(lp["attn"], x, cfg, self.ctx, pos, causal=False)
            x = x + o
            x = x + L.ffn_apply(lp["ffn"], x, cfg, self.ctx, gelu=True)
            return x, None

        x, _ = flags.scan(jax.checkpoint(body), frames, params["encoder"]["layers"])
        return rms_norm(x, params["encoder"]["ln"], cfg.norm_eps)

    # ------------------------------------------------------------- full pass
    def _group_full(self, x, gp, positions, cross_src, want_cache: bool):
        cfg, ctx = self.cfg, self.ctx
        caches: Dict[str, Any] = {}
        for i, (mixer, ffn, cross) in enumerate(self.kinds):
            sp = gp[f"slot{i}"]
            if mixer == "attn":
                o, c = L.attn_full(sp["attn"], x, cfg, ctx, positions,
                                   want_cache=want_cache)
            else:
                o, c = M2.ssm_full(sp["ssm"], x, cfg, ctx, want_cache=want_cache)
            x = x + o
            if want_cache:
                caches[f"slot{i}"] = c
            if cross:
                o, cc = L.attn_full(sp["cross"], x, cfg, ctx, positions,
                                    kv_src=cross_src, use_rope=False,
                                    want_cache=want_cache)
                x = x + o
                if want_cache:
                    caches[f"slot{i}_cross"] = cc
            if ffn == "dense":
                x = x + L.ffn_apply(sp["ffn"], x, cfg, ctx, gelu=cfg.ffn_gelu)
            elif ffn == "moe":
                x = x + MOE.moe_apply(sp["moe"], x, cfg, ctx)
        return x, caches

    def backbone(self, params, tokens, extras=None, want_cache=False,
                 remat=True):
        """tokens [B,S] -> final-normed hidden [B,S,D] (+ caches if asked)."""
        cfg = self.cfg
        extras = extras or {}
        x = self.embed(params, tokens)
        positions = jnp.arange(tokens.shape[1])[None, :]
        cross_src = None
        if cfg.encoder is not None:
            cross_src = self.encode(params, extras["frames"])
        elif cfg.cross_attn is not None:
            cross_src = extras["ctx_embeds"]

        def body(x, gp):
            x, caches = self._group_full(x, gp, positions, cross_src, want_cache)
            return x, caches if want_cache else None

        if remat:
            body = jax.checkpoint(body)
        x, caches = flags.scan(body, x, params["groups"])
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        if want_cache:
            return x, caches
        return x

    # ----------------------------------------------------------------- loss
    def loss(self, params, tokens, labels, extras=None):
        """Mean next-token cross-entropy, chunked over the sequence so the
        [B,S,V] logits are never materialized at once."""
        hidden = self.backbone(params, tokens, extras)
        hidden = self.ctx.cs(hidden, "batch", None, None)
        W = self.unembed_weight(params)
        B, S, D = hidden.shape
        Vp = self.vocab_padded
        cq = min(512, S)
        while S % cq:
            cq -= 1
        nc = S // cq
        hs = hidden.reshape(B, nc, cq, D).swapaxes(0, 1)
        ls = labels.reshape(B, nc, cq).swapaxes(0, 1)

        def step(acc, inp):
            hc, lc = inp
            logits = (hc @ W).astype(jnp.float32)          # [B,cq,Vp]
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.sum(logits * jax.nn.one_hot(lc, Vp, dtype=logits.dtype),
                         axis=-1)
            return acc + jnp.sum(lse - ll), None

        # checkpoint: recompute the [B,cq,V] logits chunk in backward instead
        # of saving every chunk's logits (that would be the full [B,S,V])
        total, _ = flags.scan(jax.checkpoint(step),
                              jnp.zeros((), jnp.float32), (hs, ls))
        return total / (B * S)

    # --------------------------------------------------------------- prefill
    def prefill(self, params, tokens, extras=None):
        """Returns (last-token logits [B,Vp], cache over the prompt)."""
        hidden, caches = self.backbone(params, tokens, extras, want_cache=True,
                                       remat=False)
        last = hidden[:, -1]
        logits = (last @ self.unembed_weight(params)).astype(jnp.float32)
        return logits, caches

    @property
    def supports_prefix_reuse(self) -> bool:
        """Prefix-cache eligibility: suffix prefill is only defined for
        pure causal self-attention stacks (every slot an attn mixer, no
        cross-attention, no encoder). SSM/hybrid state is positionally
        recurrent and cannot resume from cached pages."""
        cfg = self.cfg
        return (cfg.encoder is None and cfg.cross_attn is None
                and all(mx == "attn" and not cross
                        for mx, _, cross in self.kinds))

    def prefill_suffix(self, params, tokens, prefix_k, prefix_v):
        """Prefill only the unmatched suffix of a prompt.

        ``tokens`` [B,S] are the suffix tokens; ``prefix_k``/``prefix_v``
        [L,P,Hkv,hd] the cached prefix KV in the arena's stacked-layer
        layout (slot ``a``, group ``g`` at layer ``a * n_groups + g``, the
        same layout :meth:`paged_kv_layout` publishes). Per-row arithmetic
        matches :meth:`prefill` exactly (see ``layers.attn_suffix``), so the
        resulting logits and suffix KV are bitwise identical to a full
        prefill of prefix+suffix. Returns (last-token logits [B,Vp] f32,
        k_sfx, v_sfx [L,B,S,Hkv,hd]).
        """
        assert self.supports_prefix_reuse, self.cfg.name
        cfg = self.cfg
        A, G = len(self.kinds), self.n_groups
        P_pre = prefix_k.shape[1]
        S = tokens.shape[1]
        x = self.embed(params, tokens)
        positions = P_pre + jnp.arange(S)[None, :]
        # [A*G, P, Hkv, hd] -> [G, A, P, Hkv, hd] so groups scan on axis 0
        shp = (A, G) + prefix_k.shape[1:]
        pk_gs = prefix_k.reshape(shp).transpose(1, 0, 2, 3, 4)
        pv_gs = prefix_v.reshape(shp).transpose(1, 0, 2, 3, 4)

        def body(x, inp):
            gp, pk_g, pv_g = inp
            ks, vs = [], []
            for i, (mixer, ffn, _) in enumerate(self.kinds):
                sp = gp[f"slot{i}"]
                o, k_new, v_new = L.attn_suffix(sp["attn"], x, cfg, self.ctx,
                                                positions, pk_g[i], pv_g[i])
                x = x + o
                ks.append(k_new)
                vs.append(v_new)
                if ffn == "dense":
                    x = x + L.ffn_apply(sp["ffn"], x, cfg, self.ctx,
                                        gelu=cfg.ffn_gelu)
                elif ffn == "moe":
                    x = x + MOE.moe_apply(sp["moe"], x, cfg, self.ctx)
            return x, (jnp.stack(ks), jnp.stack(vs))

        x, (k_ys, v_ys) = flags.scan(body, x, (params["groups"], pk_gs, pv_gs))
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = (x[:, -1] @ self.unembed_weight(params)).astype(jnp.float32)
        # ys [G, A, B, S, Hkv, hd] -> stacked-layer [A*G, B, S, Hkv, hd]
        k_sfx = k_ys.transpose(1, 0, 2, 3, 4, 5).reshape((A * G,) + k_ys.shape[2:])
        v_sfx = v_ys.transpose(1, 0, 2, 3, 4, 5).reshape((A * G,) + v_ys.shape[2:])
        return logits, k_sfx, v_sfx

    @property
    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill shares the suffix-prefill eligibility rule: the
        whole prompt context must live in paged self-attention KV, so only
        pure causal self-attention stacks qualify (SSM/hybrid state is
        positionally recurrent and cannot resume mid-prompt)."""
        return self.supports_prefix_reuse

    def prefill_chunk(self, params, k_pages, v_pages, tokens, positions,
                      block_tables, rows, offs, last_idx, attend):
        """One fixed-width prefill chunk per sequence, through the PAGED
        arena.

        tokens/positions [B,C]: a chunk of each prompt at its absolute
        positions (pad columns repeat token/position 0 and scatter to the
        null row); block_tables [B,W] plane-row indices; rows/offs [B,C]
        write coordinates of the chunk tokens; last_idx [B] the in-chunk
        index of each sequence's last real token (its logit row — only
        meaningful for the chunk that completes a prompt). ``attend`` is the
        chunked-prefill attention bound once at engine construction. C is
        fixed and B is the launch width, one of the few the engine compiles
        before first use, so no prompt length adds an executable. Returns
        (last-token logits [B,Vp] f32, k_pages, v_pages) — pages are
        donatable.
        """
        assert self.supports_chunked_prefill, self.cfg.name
        cfg = self.cfg
        bases, _, _, _, _ = self.paged_kv_layout()
        x = self.embed(params, tokens)

        def body(carry, inp):
            x, kp, vp = carry
            gp, g = inp
            for i, (mixer, ffn, _) in enumerate(self.kinds):
                sp = gp[f"slot{i}"]
                o, kp, vp = L.attn_chunk_paged(
                    sp["attn"], x, cfg, self.ctx, positions, kp, vp,
                    bases[f"slot{i}"] + g, block_tables, rows, offs, attend)
                x = x + o
                if ffn == "dense":
                    x = x + L.ffn_apply(sp["ffn"], x, cfg, self.ctx,
                                        gelu=cfg.ffn_gelu)
                elif ffn == "moe":
                    x = x + MOE.moe_apply(sp["moe"], x, cfg, self.ctx)
            return (x, kp, vp), None

        (x, k_pages, v_pages), _ = flags.scan(
            body, (x, k_pages, v_pages),
            (params["groups"], jnp.arange(self.n_groups)))
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        last = x[jnp.arange(tokens.shape[0]), last_idx]
        logits = (last @ self.unembed_weight(params)).astype(jnp.float32)
        return logits, k_pages, v_pages

    # ---------------------------------------------------------------- decode
    def _group_decode(self, x, gp, gc, positions):
        cfg, ctx = self.cfg, self.ctx
        new_c: Dict[str, Any] = {}
        for i, (mixer, ffn, cross) in enumerate(self.kinds):
            sp = gp[f"slot{i}"]
            if mixer == "attn":
                o, c = L.attn_decode(sp["attn"], x, gc[f"slot{i}"], cfg, ctx,
                                     positions)
            else:
                o, c = M2.ssm_decode(sp["ssm"], x, gc[f"slot{i}"], cfg, ctx)
            x = x + o
            new_c[f"slot{i}"] = c
            if cross:
                o, cc = L.attn_decode(sp["cross"], x, gc[f"slot{i}_cross"],
                                      cfg, ctx, positions, cross=True)
                x = x + o
                new_c[f"slot{i}_cross"] = cc
            if ffn == "dense":
                x = x + L.ffn_apply(sp["ffn"], x, cfg, ctx, gelu=cfg.ffn_gelu)
            elif ffn == "moe":
                x = x + MOE.moe_apply(sp["moe"], x, cfg, ctx)
        return x, new_c

    def decode_step(self, params, cache, tokens, positions):
        """One token for every sequence. tokens [B,1]; positions [B].
        Returns (logits [B,Vp] f32, new cache — same pytree/shapes, donatable).

        The cache travels as a scan CARRY with per-group dynamic slice/update
        (not as stacked xs/ys): carries alias their buffers across iterations,
        so the multi-GB cache is updated in place instead of being stacked
        into fresh output buffers (xs/ys form peaked at ~3x cache size).
        """
        x = self.embed(params, tokens)

        def body(carry, inp):
            x, cache = carry
            gp, g = inp
            gc = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, g, 0, keepdims=False),
                cache)
            x, new_c = self._group_decode(x, gp, gc, positions)
            cache = jax.tree.map(
                lambda a, n: lax.dynamic_update_index_in_dim(a, n, g, 0),
                cache, new_c)
            return (x, cache), None

        (x, new_cache), _ = flags.scan(
            body, (x, cache),
            (params["groups"], jnp.arange(self.n_groups)))
        x = rms_norm(x, params["final_ln"], self.cfg.norm_eps)
        last = x[:, 0]
        if self.ctx.mode == "fsdp_cp":
            # stationary unembed: psum a [B, V/tp] partial instead of
            # all-gathering the f32 lm_head (311MB/step for qwen1.5-110b)
            last = self.ctx.cs(last, None, "fsdp")
        logits = (last @ self.unembed_weight(params)).astype(jnp.float32)
        return logits, new_cache

    # ------------------------------------------------------- paged serving
    def paged_kv_layout(self):
        """Self-attention KV geometry for the serving arena.

        Returns ``(bases, n_layers, Hkv, hd, dtype)`` where ``bases`` maps
        each self-attention slot name to its layer base in the stacked plane
        layout: slot ``a`` (in slot order), group ``g`` lives at stacked
        layer ``a * n_groups + g``. ``n_layers == 0`` means nothing to page
        (pure-SSM model: recurrent state only).
        """
        attn = [f"slot{i}" for i, (mx, _, _) in enumerate(self.kinds)
                if mx == "attn"]
        bases = {s: a * self.n_groups for a, s in enumerate(attn)}
        return (bases, len(attn) * self.n_groups, self.cfg.n_kv_heads,
                self.cfg.head_dim_, self.cfg.dtype)

    def _group_decode_paged(self, x, gp, gc, g, kp, vp, block_tables,
                            seq_lens, rows, offs, positions, bases, attend,
                            inline=False):
        """_group_decode with self-attention KV read/written through arena
        pages; ``gc``/``new_c`` carry only the non-paged (SSM / cross)
        entries."""
        cfg, ctx = self.cfg, self.ctx
        new_c: Dict[str, Any] = {}
        for i, (mixer, ffn, cross) in enumerate(self.kinds):
            sp = gp[f"slot{i}"]
            if mixer == "attn":
                o, kp, vp = L.attn_decode_paged(
                    sp["attn"], x, cfg, ctx, positions, kp, vp,
                    bases[f"slot{i}"] + g, block_tables, seq_lens, rows,
                    offs, attend, inline=inline)
            else:
                o, c = M2.ssm_decode(sp["ssm"], x, gc[f"slot{i}"], cfg, ctx)
                new_c[f"slot{i}"] = c
            x = x + o
            if cross:
                o, cc = L.attn_decode(sp["cross"], x, gc[f"slot{i}_cross"],
                                      cfg, ctx, positions, cross=True)
                x = x + o
                new_c[f"slot{i}_cross"] = cc
            if ffn == "dense":
                x = x + L.ffn_apply(sp["ffn"], x, cfg, ctx, gelu=cfg.ffn_gelu)
            elif ffn == "moe":
                x = x + MOE.moe_apply(sp["moe"], x, cfg, ctx)
        return x, new_c, kp, vp

    def decode_step_paged(self, params, state_cache, k_pages, v_pages,
                          block_tables, seq_lens, rows, offs, tokens,
                          positions, attend, inline=False):
        """One token for every sequence through the PAGED KV arena.

        Mirrors :meth:`decode_step`, but self-attention KV lives in the
        shared node arena plane (``k_pages``/``v_pages``, written in place
        via scatter and read through per-sequence ``block_tables``);
        ``state_cache`` carries only SSM state/conv and static cross-attn
        entries (see :meth:`state_cache_specs`). ``attend`` is the paged
        attention implementation bound once at engine construction.
        Returns (logits [B,Vp] f32, state_cache, k_pages, v_pages) — all
        cache-like arguments are donatable.
        """
        bases, _, _, _, _ = self.paged_kv_layout()
        x = self.embed(params, tokens)

        def body(carry, inp):
            x, sc, kp, vp = carry
            gp, g = inp
            gc = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, g, 0, keepdims=False),
                sc)
            x, new_c, kp, vp = self._group_decode_paged(
                x, gp, gc, g, kp, vp, block_tables, seq_lens, rows, offs,
                positions, bases, attend, inline=inline)
            sc = jax.tree.map(
                lambda a, n: lax.dynamic_update_index_in_dim(a, n, g, 0),
                sc, new_c)
            return (x, sc, kp, vp), None

        (x, state_cache, k_pages, v_pages), _ = flags.scan(
            body, (x, state_cache, k_pages, v_pages),
            (params["groups"], jnp.arange(self.n_groups)))
        x = rms_norm(x, params["final_ln"], self.cfg.norm_eps)
        logits = (x[:, 0] @ self.unembed_weight(params)).astype(jnp.float32)
        return logits, state_cache, k_pages, v_pages

    @property
    def supports_decode_horizon(self) -> bool:
        """Multi-token decode-horizon eligibility: the horizon loop carries
        only paged pages + positions between iterations, so every layer's
        context must live in paged self-attention KV — the same pure causal
        self-attention condition as prefix reuse (SSM/hybrid state and
        cross-attention caches would need in-loop state threading; those
        models fall back to one-token steps)."""
        return self.supports_prefix_reuse

    def decode_horizon(self, params, state_cache, k_pages, v_pages,
                       block_tables, positions, last_tokens, live, rem, cap,
                       eos, s_max, *, attend, horizon: int,
                       page_tokens: int):
        """Run up to ``horizon`` greedy decode iterations entirely on device.

        One jitted program replaces ``horizon`` host round-trips: a
        ``lax.fori_loop`` whose body is exactly :meth:`decode_step_paged`
        (same per-lane arithmetic as the one-token engine path — greedy
        parity is structural, not approximate), with on-device argmax
        sampling, in-loop paged-KV writes (iteration ``h`` reads its own
        write inline and iterations ``> h`` read it from the pages), and a
        per-lane stop mask.

        block_tables [B, W] plane rows; positions [B] next write position;
        last_tokens [B] the token feeding iteration 0; live [B] bool lanes
        decoding this launch; rem [B] tokens until ``max_new``; cap [B]
        page-granted emission budget (freezes a lane WITHOUT finishing it —
        truncation backpressure stays host-decided); eos [B] end token or -1;
        s_max scalar sequence window. A lane freezes permanently once it
        emits its stage-final token (``rem``/``eos``/``s_max``, the same
        predicate the engine applies after each one-token step) or exhausts
        ``cap``; frozen lanes emit the -1 sentinel, write only to the null
        row, and attend over a clamped length-1 window whose output is
        discarded.

        Returns ``(tokens [B, horizon] int32 with -1 in frozen lanes,
        positions, state_cache, k_pages, v_pages)`` — ONE host sync fetches
        the token block; positions stay on device as the next launch's
        persistent buffer.
        """
        assert self.supports_decode_horizon, self.cfg.name
        B = block_tables.shape[0]
        lanes = jnp.arange(B)
        out0 = jnp.full((B, horizon), -1, jnp.int32)
        live = live.astype(jnp.bool_)

        def body(h, carry):
            out, live, pos, last, rem, cap, sc, kp, vp = carry
            adv = live.astype(jnp.int32)
            # frozen/idle lanes read+write the reserved null row (row 0),
            # exactly like the one-token path's idle slots
            rows = jnp.where(live, block_tables[lanes, pos // page_tokens], 0)
            offs = jnp.where(live, pos % page_tokens, 0)
            seq_lens = jnp.where(live, pos + 1, 1)
            logits, sc, kp, vp = self.decode_step_paged(
                params, sc, kp, vp, block_tables, seq_lens, rows, offs,
                last[:, None], pos, attend, inline=True)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = out.at[:, h].set(jnp.where(live, nxt, -1))
            pos = pos + adv
            rem = rem - adv
            cap = cap - adv
            last = jnp.where(live, nxt, last)
            stop = ((rem <= 0) | ((eos >= 0) & (nxt == eos))
                    | (pos >= s_max - 1) | (cap <= 0))
            return (out, live & ~stop, pos, last, rem, cap, sc, kp, vp)

        out, live, positions, last_tokens, rem, cap, state_cache, k_pages, \
            v_pages = lax.fori_loop(
                0, horizon, body,
                (out0, live, positions, last_tokens, rem, cap, state_cache,
                 k_pages, v_pages))
        return out, positions, state_cache, k_pages, v_pages

    # ----------------------------------------------------------- cache specs
    def _slot_cache_spec(self, kind: SlotKind, batch: int, seq: int):
        """ShapeDtypeStruct + PartitionSpec for one slot's decode cache."""
        cfg, ctx = self.cfg, self.ctx
        mixer, _, cross = kind
        out = {}
        if mixer == "attn":
            Hkv, hd = cfg.n_kv_heads, cfg.head_dim_
            shp = (batch, seq, Hkv, hd)
            spec = ctx.spec("batch", "kv_sp", None, None, dims=shp)
            out["self"] = ({"k": (shp, cfg.dtype, spec),
                            "v": (shp, cfg.dtype, spec)})
        else:
            s = cfg.ssm
            H, Pd = s.n_heads(cfg.d_model), s.head_dim
            shp_s = (batch, H, s.d_state, Pd)
            shp_c = (batch, s.conv_dim - 1, s.d_inner(cfg.d_model))
            out["self"] = {
                "state": (shp_s, jnp.float32,
                          ctx.spec("batch", "tp", None, None, dims=shp_s)),
                "conv": (shp_c, cfg.dtype,
                         ctx.spec("batch", None, "tp", dims=shp_c)),
            }
        if cross:
            n_ctx = (cfg.encoder.n_frames if cfg.encoder is not None
                     else cfg.cross_attn.n_ctx_tokens)
            Hkv, hd = cfg.n_kv_heads, cfg.head_dim_
            shp = (batch, n_ctx, Hkv, hd)
            spec = ctx.spec("batch", "kv_sp", None, None, dims=shp)
            out["cross"] = {"k": (shp, cfg.dtype, spec),
                            "v": (shp, cfg.dtype, spec)}
        return out

    def cache_specs(self, batch: int, seq: int):
        """(ShapeDtypeStruct tree, PartitionSpec tree) for the decode cache."""
        g = self.n_groups
        structs: Dict[str, Any] = {}
        specs: Dict[str, Any] = {}

        def expand(raw):  # (shape, dtype, spec) -> stacked struct/spec
            shp, dt, spec = raw
            return (jax.ShapeDtypeStruct((g,) + shp, dt),
                    P(*((None,) + tuple(spec))))

        for i, kind in enumerate(self.kinds):
            raw = self._slot_cache_spec(kind, batch, seq)
            for part, entries in raw.items():
                name = f"slot{i}" if part == "self" else f"slot{i}_cross"
                st, sp = {}, {}
                for kname, r in entries.items():
                    st[kname], sp[kname] = expand(r)
                structs[name] = st
                specs[name] = sp
        return structs, specs

    def state_cache_specs(self, batch: int, seq: int):
        """:meth:`cache_specs` minus self-attention K/V — those pages live in
        the serving arena; what remains (SSM state/conv, static cross-attn
        K/V) is the per-slot state an engine still holds densely."""
        structs, specs = self.cache_specs(batch, seq)
        for i, (mixer, _, _) in enumerate(self.kinds):
            if mixer == "attn":
                structs.pop(f"slot{i}", None)
                specs.pop(f"slot{i}", None)
        return structs, specs
