"""Node-level multi-model runtime: real model colocation on one device.

Each node is given its device: its weights, KV arena planes, engine state
and step inputs all live there, so replicas on a multi-chip host each hold
their own copy on their own chip.

Holds a zoo of (small) models; weights move between DEVICE (jnp arrays) and
HOST (numpy) following the hierarchical residency manager — a Sleeping model
keeps its compiled executable cache (the CUDA-graph analogue: jax.jit cache
keyed by shapes survives offload) while its weights live in host RAM.
Exports the readiness / headroom signals (NodeSignal) the cross-cluster
scheduler consumes.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.core.predictor.cost_model import ModelProfile, hardware_spec
from repro.core.runtime.accounting import MemoryAccountant
from repro.core.runtime.coordination import (EngineInfo, EngineState,
                                             plan_degradation)
from repro.core.runtime.residency import HierarchicalResidency, ModelState
from repro.core.sched.fitness import NodeSignal
from repro.models.transformer import Model
from repro.serving.engine import Engine, Request
from repro.serving.kv_arena import KVArena
from repro.serving.telemetry import span


def _tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


class NodeRuntime:
    def __init__(self, node_id: int, cluster_id: int,
                 zoo: Dict[str, Model], host_params: Dict[str, Any],
                 hbm_budget: float = 2e9, max_slots: int = 4,
                 s_max: int = 256, ctx_bytes: int = 8 << 20,
                 page_tokens: int = 16, prefix_cache: bool = False,
                 prefix_cache_pages: int = 256,
                 max_batch_tokens: Optional[int] = None,
                 prefill_chunk_tokens: int = 0,
                 decode_horizon: int = 1,
                 device: Optional[jax.Device] = None):
        self.node_id = node_id
        # the node's device (default: the first local one)
        self.device = device if device is not None else jax.local_devices()[0]
        self.cluster_id = cluster_id
        self.zoo = zoo
        self.host_params = host_params      # numpy trees (host tier)
        self.device_params: Dict[str, Any] = {}
        self.engines: Dict[str, Engine] = {}
        self.acc = MemoryAccountant(m_total=hbm_budget, m_other=16 << 20)
        # ONE physical paged-KV arena per node: every colocated engine's
        # pool grants map onto it 1:1 (§III.C spatial multiplexing)
        self.arena = KVArena(page_tokens=page_tokens)
        self.prefix_cfg = None
        if prefix_cache:
            from repro.serving.prefix_cache import PrefixCacheConfig
            self.prefix_cfg = PrefixCacheConfig(max_pages=prefix_cache_pages)
        self.ctx_bytes = ctx_bytes
        self.max_slots = max_slots
        self.s_max = s_max
        # engine iteration-scheduler knobs (chunked prefill / token budget),
        # forwarded to every colocated engine at activation
        self.max_batch_tokens = max_batch_tokens
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.decode_horizon = decode_horizon
        # routing: stages submitted here that have a parent stage, and
        # those of them whose parent ran on this node
        self.stat_child_stages = 0
        self.stat_child_affine = 0
        # wall seconds spent inside step()
        self.stat_step_s = 0.0
        hw = hardware_spec(self.device)
        profiles = {
            name: ModelProfile(
                name=name, weight_bytes=_tree_bytes(host_params[name]),
                ctx_bytes=ctx_bytes,
                # dtype-aware: must match the engine pool's per-token charge
                # (reduced smoke configs run f32, production configs bf16)
                alpha_bytes_per_token=m.cfg.kv_bytes_per_token(
                    dtype_bytes=jax.numpy.dtype(m.cfg.dtype).itemsize),
                state_bytes=m.cfg.ssm_state_bytes(),
                prefill_flops_per_token=2.0 * m.cfg.active_param_count(),
                decode_bytes_per_token=2.0 * m.cfg.active_param_count(),
                hw=hw)
            for name, m in zoo.items()}
        self.profiles = profiles
        self.residency = HierarchicalResidency(
            profiles, c_gpu=hbm_budget * 0.8, c_cpu=64e9, c_disk=1e12)
        # host tier is where everything starts
        for name in zoo:
            self.residency.state[name] = ModelState.CPU
            self.residency.lru["cpu"][name] = profiles[name].weight_bytes

    # ------------------------------------------------------------ residency
    def activate(self, name: str) -> float:
        """Make `name` servable; returns measured activation seconds."""
        moved = (0 if name in self.device_params
                 else self.profiles[name].weight_bytes)
        with span("node.activate", model=name, bytes=moved):
            t0 = time.perf_counter()
            # models with ANY queued work are in-flight: evicting one whose
            # requests are still waiting for admission would strand them
            # (step() skips off-device engines)
            self.residency.pinned = {m for m, e in self.engines.items()
                                     if e.active or e.waiting}
            ok, _ = self.residency.ensure_gpu(name)
            if not ok:
                raise RuntimeError(f"cannot activate {name}")
            # apply evictions the residency manager decided
            for m, st in self.residency.state.items():
                if st in (ModelState.SLEEPING, ModelState.CPU) \
                        and m in self.device_params:
                    self._offload(m)
            if name not in self.device_params:
                self.device_params[name] = jax.tree.map(
                    lambda x: jax.device_put(x, self.device),
                    self.host_params[name])
                self.acc.register_weights(
                    name, self.profiles[name].weight_bytes)
                self.acc.register_context(name, self.ctx_bytes)
            if name not in self.engines:
                # the engine makes its state cache and arena planes under the
                # default device: pin that to this node's
                with jax.default_device(self.device):
                    self.engines[name] = Engine(
                        self.zoo[name], self.device_params[name], self.acc,
                        max_slots=self.max_slots, s_max=self.s_max,
                        arena=self.arena, prefix_cache=self.prefix_cfg,
                        prefix_ns=name,
                        max_batch_tokens=self.max_batch_tokens,
                        prefill_chunk_tokens=self.prefill_chunk_tokens,
                        decode_horizon=self.decode_horizon)
            else:
                self.engines[name].params = self.device_params[name]
            return time.perf_counter() - t0

    def _offload(self, name: str) -> None:
        """Device -> host (weights only; jit executable cache survives —
        that is what makes re-activation cheap for Sleeping models). The
        engine's KV — arena pages, block tables and the dense state cache —
        is freed and de-accounted here: an offloaded model holds no silent
        device-resident KV (leak fix)."""
        eng = self.engines.get(name)
        if eng is not None:
            eng.release_kv()
            eng.params = None     # the engine's reference would pin them
        self.device_params.pop(name, None)
        self.acc.unregister_weights(name)
        if self.residency.state[name] is ModelState.CPU:
            self.acc.unregister_context(name)

    def sleep(self, name: str) -> None:
        self.residency.sleep(name)
        self._offload(name)

    # -------------------------------------------------------------- serving
    def submit(self, model: str, req: Request) -> None:
        with span("node.submit", node=self.node_id, req=req.req_id):
            if req.has_parent:
                self.stat_child_stages += 1
                self.stat_child_affine += req.parent_node == self.node_id
            if model not in self.device_params:
                self.activate(model)
            self.engines[model].submit(req)

    def preempt(self, model: str, req_id: int) -> Optional[Request]:
        """Boundary-preempt a request on this node (waiting or active);
        returns the withdrawn Request (partial output discarded) or None."""
        eng = self.engines.get(model)
        return None if eng is None else eng.evict(req_id)

    def t_act(self, model: str) -> float:
        """Estimated activation latency (no side effects) — the T_act of
        Eq. 6 that the cross-cluster router consumes."""
        return self.residency.activation_latency(model)

    # ----------------------------------------------- admission (Alg. 2 aware)
    def _busy_models(self) -> set:
        return {m for m, e in self.engines.items() if e.active or e.waiting}

    def can_admit(self, r_need: float, model: Optional[str] = None) -> bool:
        """Eviction-aware KV admission feasibility (mirrors SimNode):
        everything except in-flight models' weights and contexts can be
        reclaimed by degradation levels 1-2 before the stage lands."""
        extra = 0.0
        if model is not None:
            if model not in self.acc.weights:
                extra += self.profiles[model].weight_bytes
            if model not in self.acc.ctx:
                extra += self.profiles[model].ctx_bytes
        if self.acc.can_admit(r_need + extra):
            return True
        if model is None:
            return False
        active = self._busy_models() | {model}
        floor = sum(self.profiles[m].weight_bytes + self.profiles[m].ctx_bytes
                    for m in active)
        # in-flight engines also keep their dense state caches resident
        floor += sum(e._state_bytes for m2, e in self.engines.items()
                     if m2 in active)
        return (floor + self.acc.m_kv + self.acc.m_other + r_need
                <= self.acc.m_total)

    def degradation_cost(self, r_need: float) -> Optional[float]:
        """C_deg for admitting r_need via Algorithm 2 (None = impossible) —
        the live counterpart of SimNode.degradation_cost, built from the
        real engines' in-flight state."""
        shortfall = r_need - self.acc.headroom
        if shortfall <= 0:
            return 0.0
        busy = self._busy_models()
        engines = []
        for m in self.residency.warm_set():
            st = self.residency.state[m]
            eng = self.engines.get(m)
            kv_tokens = (sum(len(r.tokens) + len(r.out)
                             for r in eng.active.values()) if eng else 0)
            prof = self.profiles[m]
            engines.append(EngineInfo(
                model=m,
                state=(EngineState.ACTIVE if m in busy else
                       EngineState.IDLE if st is ModelState.RUNNING
                       else EngineState.SLEEPING),
                weight_bytes=prof.weight_bytes,
                ctx_bytes=prof.ctx_bytes,
                kv_bytes=float((eng.alpha if eng else 0) * kv_tokens),
                kv_tokens=kv_tokens,
                decode_tok_per_s=1.0 / max(prof.t_decode, 1e-9)))
        plan = plan_degradation(shortfall, engines,
                                next(iter(self.profiles.values())).hw)
        return None if plan is None else plan.c_deg

    def make_room(self, r_need: float) -> None:
        """Degradation levels 0-2 (Algorithm 2's cheap prefix) on the live
        node: trim cached-but-unreferenced prefix pages first, then sleep
        idle engines, then drop sleeping warm contexts, until r_need fits.
        In-flight engines are never touched."""
        idx = self.arena.prefix_index
        if idx is not None:
            while idx.entries and not self.acc.can_admit(r_need):
                if not idx.trim(8):                   # level 0
                    break
        busy = self._busy_models()
        for m in list(self.residency.lru["gpu"]):
            if self.acc.can_admit(r_need):
                return
            if m not in busy and self.residency.state[m] is ModelState.RUNNING:
                self.sleep(m)                         # level 1
        for m, st in list(self.residency.state.items()):
            if self.acc.can_admit(r_need):
                return
            if m not in busy and st is ModelState.SLEEPING:
                self.residency.demote_context(m)      # level 2
                self.acc.unregister_context(m)

    def has_work(self) -> bool:
        """True while any colocated engine has waiting or active requests —
        the free-running worker loop and the wall-clock gateway step/poll
        only nodes for which this holds."""
        return any(e.waiting or e.active for e in self.engines.values())

    def step(self) -> Dict[str, list]:
        t0 = time.perf_counter()
        out = {}
        with span("node.step", node=self.node_id):
            for name, eng in self.engines.items():
                if (eng.waiting or eng.active) \
                        and name not in self.device_params:
                    self.activate(name)   # self-heal: offloaded, work queued
                if name in self.device_params and (eng.waiting or eng.active):
                    # step inputs, plane growth and state caches go to this
                    # node's device
                    with jax.default_device(self.device):
                        eng.step()
                if eng.finished:
                    out[name] = eng.finished[:]
                    eng.finished.clear()
        self.stat_step_s += time.perf_counter() - t0
        return out

    # -------------------------------------------------------------- signals
    def kv_overcommit_ratio(self) -> float:
        """Live counterpart of Table V's overcommit: total virtual KV the
        colocated engines advertise over the PEAK physical KV ever mapped in
        the shared arena. > 1 means spatial multiplexing is really happening
        (the engines together promise more KV than was ever resident).
        0.0 until any KV was physically mapped (ratio undefined)."""
        if self.arena.peak_mapped_bytes <= 0:
            return 0.0
        virt = sum(e.pool.virtual_total() for e in self.engines.values())
        return virt / self.arena.peak_mapped_bytes

    def kv_stats(self) -> Dict[str, float]:
        """Arena/overcommit snapshot consumed by gateway end-of-run metrics
        — one picklable dict so worker processes report it in a single
        round trip."""
        out = {"n_engines": len(self.engines),
               "kv_overcommit_ratio": self.kv_overcommit_ratio(),
               "arena_peak_pages": int(self.arena.peak_mapped_pages),
               "arena_utilization": float(self.arena.utilization()),
               "pages_aliased": int(self.arena.pages_aliased),
               "cow_copies": int(self.arena.cow_copies),
               # iteration-scheduler telemetry, summed over engines
               "engine_prefill_tokens": sum(
                   e.stat_prefill_tokens for e in self.engines.values()),
               "engine_decode_tokens": sum(
                   e.stat_decode_tokens for e in self.engines.values()),
               "engine_prefill_compiles": sum(
                   e.prefill_compiles for e in self.engines.values()),
               "engine_fused_steps": sum(
                   e.stat_fused_steps for e in self.engines.values()),
               "engine_steps": sum(
                   e.stat_steps for e in self.engines.values()),
               # decode-horizon telemetry: fused multi-token launches and
               # host round-trips (one per horizon launch vs one per token)
               "engine_horizon_steps": sum(
                   e.stat_horizon_steps for e in self.engines.values()),
               "engine_decode_syncs": sum(
                   e.stat_decode_syncs for e in self.engines.values()),
               # chunked-prefill fill: launches, the lanes they launched
               # (bucket widths) and the real prompt tokens they ran, of
               # lanes x prefill_chunk_tokens
               "engine_chunk_launches": sum(
                   e.stat_chunk_launches for e in self.engines.values()),
               "engine_chunk_lanes": sum(
                   e.stat_chunk_lanes for e in self.engines.values()),
               "engine_chunk_tokens": sum(
                   e.stat_chunk_tokens for e in self.engines.values()),
               # decode page growth under pressure: prefix pins trimmed,
               # lane-steps that waited, lanes preempted
               "engine_pin_trims": sum(
                   e.stat_pin_trims for e in self.engines.values()),
               "engine_grow_waits": sum(
                   e.stat_grow_waits for e in self.engines.values()),
               "engine_preemptions": sum(
                   e.stat_preemptions for e in self.engines.values()),
               # routing: child stages submitted here, and those placed on
               # the node their parent ran on
               "node_child_stages": self.stat_child_stages,
               "node_child_affine": self.stat_child_affine,
               # wall seconds inside step(): summed over nodes and divided
               # by a window, how many nodes stepped at once on average
               "node_step_s": self.stat_step_s}
        if self.arena.prefix_index is not None:
            out.update(self.arena.prefix_index.stats())
        return out

    @property
    def page_tokens(self) -> int:
        return self.arena.page_tokens

    def signal(self) -> NodeSignal:
        warm = {m: self.residency.activation_latency(m)
                for m in self.residency.warm_set()}
        qd = float(np.mean([len(e.waiting) for e in self.engines.values()])
                   ) if self.engines else 0.0
        return NodeSignal(node_id=self.node_id, cluster_id=self.cluster_id,
                          headroom=self.acc.headroom, queue_delay_s=qd,
                          warm_models=warm, total_hbm=self.acc.m_total,
                          prefix_digests=self.arena.prefix_digest_summary())
