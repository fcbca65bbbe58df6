"""Live cluster topology: fleets of real ``NodeRuntime`` engines spread
across simulated-RTT clusters, plus the trace -> live-workload adapter.

This is the prototype-experiment substrate of the paper (§IV "prototype"):
every node holds the same (tiny, structurally faithful) model zoo and real
JAX engines; cross-cluster effects (RTT, cold starts) enter through the
gateway's deterministic virtual clock rather than wall-clock sleeps.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core.predictor.features import StageObservation
from repro.core.topology import DEFAULT_RTT
from repro.data.tracegen import JobRecord
from repro.models import build_model
from repro.serving.node_runtime import NodeRuntime

# default live zoo: three distinct families colocated per node (attention,
# code-tuned attention, SSM) — the Table-IV colocation regime in miniature
DEFAULT_ZOO = ("qwen3-8b", "starcoder2-15b", "mamba2-2.7b")


@dataclasses.dataclass
class NodeSpec:
    cluster_id: int
    hbm_budget: float = 1.2e9
    max_slots: int = 4
    s_max: int = 64
    # cross-stage prefix-cache plane (off by default: disabled fleets stay
    # bit-identical to pre-prefix-cache behavior)
    prefix_cache: bool = False
    prefix_cache_pages: int = 256
    # engine iteration scheduler (0 = monolithic prefill, bit-identical to
    # pre-chunking behavior; > 0 streams prompts in fixed-width chunks
    # fused with decode, budgeted by max_batch_tokens per iteration)
    max_batch_tokens: Optional[int] = None
    prefill_chunk_tokens: int = 0
    # decode horizon (1 = one host sync per decode iteration, bit-identical
    # to pre-horizon behavior; H > 1 fuses up to H decode iterations into
    # one jitted on-device loop with a single host sync per launch)
    decode_horizon: int = 1


@dataclasses.dataclass
class ClusterSpec:
    """Fleet description consumed by ``build_fleet``."""
    nodes: Tuple[NodeSpec, ...] = (NodeSpec(0), NodeSpec(0, hbm_budget=0.8e9),
                                   NodeSpec(1))
    rtt_s: np.ndarray = dataclasses.field(
        default_factory=lambda: DEFAULT_RTT.copy())
    model_names: Tuple[str, ...] = DEFAULT_ZOO

    @property
    def n_clusters(self) -> int:
        return int(max(n.cluster_id for n in self.nodes)) + 1


def host_params(model, seed: int) -> Any:
    """A model's host-tier parameter tree (numpy), drawn from ``seed`` on the
    host's CPU device: the host tier is host memory by design, so nothing is
    drawn on an accelerator (no f32 draw of a full-width leaf there, and no
    device -> host -> device round trip before the first activation)."""
    with jax.default_device(jax.devices("cpu")[0]):
        return jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(seed)))


def build_zoo(model_names: Sequence[str] = DEFAULT_ZOO, seed: int = 1
              ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Tiny real models (reduced configs) + host-tier numpy parameter trees.
    The host trees are shared by every node of the fleet (a model registry),
    exactly as weights would be fetched from common storage."""
    zoo, host = {}, {}
    for i, name in enumerate(model_names):
        cfg = get_config(name).reduced()
        m = build_model(cfg)
        zoo[name] = m
        host[name] = host_params(m, seed + i)
    return zoo, host


def worker_specs(spec: ClusterSpec, seed: int = 1,
                 worker_xla_flags: Optional[str] = None) -> List[Any]:
    """The picklable per-node ``WorkerSpec`` list for a cluster spec —
    what both worker backends ship to their children, and what
    ``connect_fleet`` sends to standalone remote workers."""
    from repro.serving.worker import WorkerSpec
    return [WorkerSpec(node_id=nid, cluster_id=ns.cluster_id,
                       model_names=tuple(spec.model_names),
                       hbm_budget=ns.hbm_budget, max_slots=ns.max_slots,
                       s_max=ns.s_max, seed=seed,
                       prefix_cache=ns.prefix_cache or None,
                       prefix_cache_pages=(ns.prefix_cache_pages
                                           if ns.prefix_cache else None),
                       max_batch_tokens=ns.max_batch_tokens,
                       prefill_chunk_tokens=(ns.prefill_chunk_tokens
                                             or None),
                       decode_horizon=(ns.decode_horizon
                                       if ns.decode_horizon > 1 else None),
                       xla_flags=worker_xla_flags)
            for nid, ns in enumerate(spec.nodes)]


def build_fleet(spec: Optional[ClusterSpec] = None,
                zoo: Optional[Dict[str, Any]] = None,
                host: Optional[Dict[str, Any]] = None,
                seed: int = 1, backend: str = "inproc",
                worker_xla_flags: Optional[str] = None,
                worker_addresses: Optional[Sequence[Any]] = None
                ) -> List[Any]:
    """Instantiate the fleet; node ids are positional.

    ``backend="inproc"`` (default) returns in-process ``NodeRuntime``
    objects, node ``i`` on local device ``i`` modulo the device count;
    ``backend="process"`` spawns one worker process per node and
    returns ``NodeHandle`` proxies (each child builds its own zoo from the
    same ``model_names`` + ``seed``, so the fleets are numerically
    identical — ``zoo``/``host`` are ignored there); ``backend="socket"``
    speaks the same protocol over the framed TCP transport — localhost
    children by default, or, when ``worker_addresses`` gives one
    "host:port" per node, workers already listening elsewhere (started
    with ``python -m repro.serving.worker --listen``).
    ``worker_xla_flags`` (worker backends only) is appended to each child's
    ``XLA_FLAGS`` before its XLA client forms — an operator knob for wall-
    clock fleets (e.g. pin workers single-threaded on hosts where process
    thread pools outnumber cores; measure first — on some hosts the pool
    wins). Leave it None for virtual-clock runs, whose bit-identical
    parity is stated for unmodified child numerics."""
    spec = spec or ClusterSpec()
    if worker_addresses is not None and backend != "socket":
        raise ValueError("worker_addresses requires backend='socket'")
    if backend in ("process", "socket"):
        from repro.serving.worker import connect_fleet, spawn_fleet
        specs = worker_specs(spec, seed=seed,
                             worker_xla_flags=worker_xla_flags)
        if worker_addresses is not None:
            return connect_fleet(worker_addresses, specs)
        return spawn_fleet(specs, backend=backend)
    if backend != "inproc":
        raise ValueError(f"unknown node backend {backend!r} "
                         "(expected 'inproc', 'process' or 'socket')")
    if zoo is None or host is None:
        zoo, host = build_zoo(spec.model_names, seed=seed)
    # one node per device, round robin: on a multi-chip host each replica
    # holds its own weights and KV on its own chip
    devices = jax.local_devices()
    fleet = []
    for nid, ns in enumerate(spec.nodes):
        fleet.append(NodeRuntime(nid, ns.cluster_id, zoo, host,
                                 device=devices[nid % len(devices)],
                                 hbm_budget=ns.hbm_budget,
                                 max_slots=ns.max_slots, s_max=ns.s_max,
                                 prefix_cache=ns.prefix_cache,
                                 prefix_cache_pages=ns.prefix_cache_pages,
                                 max_batch_tokens=ns.max_batch_tokens,
                                 prefill_chunk_tokens=ns.prefill_chunk_tokens,
                                 decode_horizon=ns.decode_horizon))
    return fleet


# ---------------------------------------------------------------------------
# Trace adapter: simulator JobRecords -> live jobs with real token prompts
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LiveStage:
    stage_id: int
    job_id: int
    deps: List[int]
    obs: StageObservation
    interactive: bool
    tokens: List[int]             # real prompt token ids
    max_new: int                  # decode budget (ground-truth len, capped)
    nominal_len: int = 0          # uncapped trace-scale output length; the
                                  # calibration target for L_hat (0 => max_new)


@dataclasses.dataclass
class LiveJob:
    job_id: int
    app: str
    interactive: bool
    arrival_s: float
    stages: List[LiveStage]
    deadline_s: float = 0.0       # filled by the gateway's SLO profiler


def _block_tokens(key: str, n: int, vocab: int) -> List[int]:
    """Token ids of a named prompt block, derived from the key ALONE (an
    rng seeded from the key's hash) — equal keys materialize to identical
    tokens in any job/stage, which is precisely the shared-prefix property
    the cross-stage prefix cache exploits. Does not touch the trace-level
    rng, so classic (block-free) traces stay byte-identical."""
    h = hashlib.blake2b(key.encode(), digest_size=8).digest()
    r = np.random.default_rng(int.from_bytes(h, "big"))
    return [int(x) for x in r.integers(0, vocab, n)]


def jobs_from_trace(trace_jobs: Sequence[JobRecord], vocab: int = 512,
                    prompt_cap: int = 16, gen_cap: int = 16,
                    n_clusters: int = 3, seed: int = 0) -> List[LiveJob]:
    """Instantiate real token payloads for a generated trace. Prompt/output
    lengths are capped so tiny smoke models execute quickly; the ORIGINAL
    observation (with its uncapped prompt_len and semantic text) is kept, so
    the predictor and router see the workload the trace describes.

    Stages carrying ``prompt_blocks`` (team traces) get their tokens from
    the named blocks instead of the shared rng: block-structured prompts
    with identical leading blocks share identical leading tokens."""
    rng = np.random.default_rng(seed)
    out: List[LiveJob] = []
    for j in trace_jobs:
        stages = []
        for s in j.stages:
            obs = s.obs
            if obs.src_cluster >= n_clusters:
                obs = dataclasses.replace(obs,
                                          src_cluster=obs.src_cluster
                                          % n_clusters)
            blocks = getattr(s, "prompt_blocks", None)
            if blocks:
                tokens: List[int] = []
                for key, n in blocks:
                    tokens += _block_tokens(key, n, vocab)
            else:
                p = int(np.clip(s.obs.prompt_len // 32, 4, prompt_cap))
                tokens = list(rng.integers(0, vocab, p))
            stages.append(LiveStage(
                stage_id=s.stage_id, job_id=j.job_id, deps=list(s.deps),
                obs=obs, interactive=s.interactive,
                tokens=tokens,
                max_new=int(np.clip(s.true_len // 16, 4, gen_cap)),
                nominal_len=int(s.true_len)))
        out.append(LiveJob(job_id=j.job_id, app=j.app,
                           interactive=j.interactive,
                           arrival_s=j.arrival_s, stages=stages))
    return out
