"""The benchmark's harness: builds a cell's fleet from its files, warms it,
serves the cell's traffic for one window on the wall clock, and records
what the window did.

Everything a cell needs is found by name: the workload entry of
``BENCHMARK.json`` names its configuration (``bench/configs/<file>``) and
its traffic mix (``bench/traffic/<mix>.json``). The program is only the
system under test: ``ClusterGateway`` over in-process ``NodeRuntime`` nodes,
one per chip, each with its ``Engine``, ``KVArena`` and prefix cache. The
harness records its own spans around the calls into those layers (a node's
``submit`` and ``step``), on the gateway's clock.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import check, traffic as traffic_gen, trace as trace_red

ROOT = Path(__file__).resolve().parents[1]

# Node settings every cell pins, passed to the program here and nowhere
# else. A later benchmark must keep them if the program drops them:
# the maestro policy; an 8-token on-device decode horizon; the cross-stage
# prefix cache; 16-token KV pages. The sizes that depend on the model
# (slots, window, chunk, arena rows) are the configuration file's.
PINS = dict(policy="maestro", decode_horizon=8, prefix_cache=True,
            page_tokens=16)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    spec: dict               # the whole BENCHMARK.json

    @property
    def serving(self) -> dict:
        return self.config["serving"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"],
                config=load_json(root / conf["file"]),
                traffic_name=w["traffic"],
                mix=load_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                spec=spec)


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file: the program's
    entry for the family, with every size taken from the file."""
    import jax.numpy as jnp
    from repro.configs import get_config
    base = get_config(conf["program_config"])
    if base.family != "dense" or not base.qk_norm:
        raise ValueError(f"{conf['program_config']}: the reference is a "
                         f"dense Qwen3 block with qk-norm")
    return dataclasses.replace(
        base, n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf["head_dim"]),
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        qkv_bias=bool(conf["attention_bias"]),
        dtype=jnp.dtype(conf["torch_dtype"]))


def kv_row_bytes(conf: dict) -> int:
    """Bytes of one arena row: K and V of one page across all layers."""
    return (2 * int(conf["num_hidden_layers"]) * PINS["page_tokens"]
            * int(conf["num_key_value_heads"]) * int(conf["head_dim"]) * 2)


# ------------------------------------------------------------------ spans

class Recorder:
    """What the window did, on the gateway's clock: per stage its engine
    submit, every token's arrival at the host, its finish; after every node
    step a sample of the KV held and the slots running."""

    def __init__(self):
        self.clock = None
        self.on = False
        self.submit_t: Dict[int, float] = {}
        self.node_of: Dict[int, int] = {}
        self.first_t: Dict[int, float] = {}
        self.last_t: Dict[int, float] = {}
        self.finish_t: Dict[int, float] = {}
        self.n_seen: Dict[int, int] = {}
        self.gaps: List[float] = []
        self.gap_t: List[float] = []
        self.tokens_t: List[tuple] = []        # (time, node, n tokens)
        self.kv: List[tuple] = []              # (node, reserved, written)
        self.active: List[tuple] = []          # (node, running, slots)
        self.truncated: set = set()
        self.out: Dict[int, List[int]] = {}    # finished stage -> tokens
        self.reqs: Dict[int, object] = {}      # stage -> engine Request

    def now(self) -> float:
        return self.clock.now()

    def submitted(self, rid: int, node: int) -> None:
        if self.on:
            self.submit_t.setdefault(rid, self.now())
            self.node_of[rid] = node

    def stepped(self, node, t1: float, out: dict) -> None:
        if not self.on:
            return
        fresh = 0
        for eng in node.engines.values():
            for req in eng.active.values():
                fresh += self._tokens(req, t1)
        for model, reqs in out.items():
            for req in reqs:
                self.reqs[req.req_id] = req
                fresh += self._tokens(req, t1)
                self.finish_t[req.req_id] = t1
                self.out[req.req_id] = list(req.out)
                if req.truncated:
                    self.truncated.add(req.req_id)
        if fresh:
            self.tokens_t.append((t1, node.node_id, fresh))
        for eng in node.engines.values():
            self.active.append((node.node_id, len(eng.active),
                                eng.max_slots))
            cursor = eng._prefill_pos        # rid -> prompt tokens run
            written = 0
            for rid, req in eng.active.items():
                self.reqs[rid] = req
                if rid in cursor:
                    written += cursor[rid]
                else:
                    written += int(eng.positions[eng.slot_of[rid]])
            if written:
                self.kv.append((node.node_id, float(node.acc.m_kv),
                                float(written * eng.alpha)))

    def _tokens(self, req, t: float) -> int:
        rid, n = req.req_id, len(req.out)
        prev = self.n_seen.get(rid, 0)
        if n < prev:                      # evicted and restarted
            self.n_seen[rid] = prev = 0
        if n == prev:
            return 0
        if prev == 0:
            self.first_t.setdefault(rid, t)
        else:
            self.gaps.append(t - self.last_t[rid])
            self.gap_t.append(t)
        self.gaps.extend([0.0] * (n - prev - 1))
        self.gap_t.extend([t] * (n - prev - 1))
        self.last_t[rid] = t
        self.n_seen[rid] = n
        return n - prev


def timed_node_class(rec: Recorder, annotate):
    """``NodeRuntime`` with the harness's spans around ``submit`` and
    ``step``."""
    from repro.serving.node_runtime import NodeRuntime

    class TimedNode(NodeRuntime):
        def submit(self, model, req):
            rec.submitted(req.req_id, self.node_id)
            return super().submit(model, req)

        def step(self):
            with annotate("bench:node.step"):
                out = super().step()
            if rec.on:
                rec.stepped(self, rec.now(), out)
            return out

    return TimedNode


# ------------------------------------------------------------------ fleet

@dataclasses.dataclass
class Fleet:
    name: str
    nodes: list
    weights: list            # per node: the weight tree on its chip
    weight_bytes: int


def build_fleet(cell: Cell, seed: int, devices, rec: Recorder,
                annotate) -> Fleet:
    """One node per chip, each holding the seed's weights on its chip (drawn
    there) and an arena of the configuration's rows, allocated whole so that
    no plane grows (and no program recompiles) in the window."""
    from repro.models import build_model
    from repro.serving.kv_arena import KVArena
    from bench import weights as W

    conf, srv = cell.config, cell.serving
    arch = arch_config(conf)
    model = build_model(arch)
    name = arch.name
    clusters = cell.mix["replica_clusters"]
    if len(clusters) != cell.chips:
        raise ValueError(f"{cell.traffic_name}: {len(clusters)} replica "
                         f"clusters for a {cell.chips}-chip cell")
    Node = timed_node_class(rec, annotate)
    nodes, trees = [], []
    rows = int(srv["kv_rows"])
    for i, dev in enumerate(devices[:cell.chips]):
        tree = W.draw(model.abstract_params(), seed, arch.vocab, dev)
        wbytes = W.tree_bytes(tree)
        limit = float(dev.memory_stats()["bytes_limit"]) \
            if dev.platform != "cpu" else float(srv["cpu_bytes_limit"])
        kv_bytes = (rows - 1) * kv_row_bytes(conf)
        # the rest of the chip is reserved as the model's context (program
        # transients), so that the accountant admits no more KV than the
        # arena holds, less its own reserve (checked below)
        ctx = limit - wbytes - kv_bytes
        if ctx <= 0:
            raise ValueError(f"{cell.config_name}: {rows} KV rows do not "
                             f"fit beside {wbytes} bytes of weights")
        node = Node(i, int(clusters[i]), {name: model}, {name: tree},
                    hbm_budget=limit, max_slots=int(srv["max_slots"]),
                    s_max=int(srv["s_max"]), ctx_bytes=int(ctx),
                    page_tokens=PINS["page_tokens"],
                    prefix_cache=PINS["prefix_cache"],
                    prefix_cache_pages=int(srv["prefix_cache_pages"]),
                    prefill_chunk_tokens=int(srv["prefill_chunk_tokens"]),
                    decode_horizon=PINS["decode_horizon"], device=dev)
        node.arena = KVArena(page_tokens=PINS["page_tokens"],
                             init_rows=rows)
        node.activate(name)
        if not 0 < node.acc.headroom <= kv_bytes:
            raise RuntimeError(f"node {i}: the accountant's KV headroom "
                               f"{node.acc.headroom} does not lie within "
                               f"the arena's {kv_bytes} bytes")
        nodes.append(node)
        trees.append(tree)
    return Fleet(name=name, nodes=nodes, weights=trees,
                 weight_bytes=W.tree_bytes(trees[0]))


def team_prefixes(cell: Cell, seed: int) -> List[List[int]]:
    """The seed's team system prompts, cached before the window: a
    deployment serving these teams holds them hot."""
    mix = cell.mix
    vocab = int(cell.config["vocab_size"])
    return [[int(t) for t in traffic_gen.block_tokens(
        traffic_gen.scoped_key(0, team, "sys"), int(mix["system_tokens"]),
        vocab, seed)] for team in range(int(mix["teams"]))]


def warm(fleet: Fleet, cell: Cell, prefixes=()) -> None:
    """Run every program the window's traffic uses, at its shapes, on every
    node: the fixed-shape prefill chunk, the one-token decode of a mixed
    iteration, the decode horizon, the copy-on-write of a partly matched
    prefix page, and the eager argmax; then forget what the warm-up
    cached, so every run's window starts from the same node, holding only
    ``prefixes`` (token lists) in its prefix cache."""
    from repro.serving.engine import Request
    srv = cell.serving
    chunk, page = int(srv["prefill_chunk_tokens"]), PINS["page_tokens"]
    vocab = int(cell.config["vocab_size"])
    rng = np.random.default_rng(0)
    a = [int(t) for t in rng.integers(0, vocab, 2 * chunk + page // 2)]
    b = [int(t) for t in rng.integers(0, vocab, chunk + 3)]
    # c shares a's first page and half of its second: a partial prefix hit
    c = a[:page + page // 2] + [int(t) for t in rng.integers(0, vocab, 40)]
    n_new = 3 * PINS["decode_horizon"] + 2
    for node in fleet.nodes:
        eng = node.engines[fleet.name]
        node.submit(fleet.name, Request(-1, a, max_new=n_new))
        for _ in range(64):
            node.step()
            if eng.active and all(r.out for r in eng.active.values()):
                break
        node.submit(fleet.name, Request(-2, b, max_new=n_new))
        node.submit(fleet.name, Request(-3, c, max_new=n_new))
        for _ in range(256):
            if not node.has_work():
                break
            node.step()
        if node.has_work():
            raise RuntimeError(f"node {node.node_id}: warm-up did not end")
        eng.finished.clear()
        node.arena.prefix_index.flush()
        for i, p in enumerate(prefixes):
            node.submit(fleet.name, Request(-16 - i, list(p), max_new=1))
        for _ in range(256):
            if not node.has_work():
                break
            node.step()
        eng.finished.clear()


def train_predictor():
    """The maestro policy's length predictor, trained as the program's live
    example trains it (300 recorded jobs of its own trace generator)."""
    from repro.core.predictor import MaestroPred, PredictorConfig
    from repro.core.predictor.gbdt import GBDTConfig
    from repro.data.tracegen import generate_trace, stratified_temporal_split
    jobs = generate_trace(300, seed=9)
    train, _ = stratified_temporal_split(jobs)
    cfg = PredictorConfig(
        cls=GBDTConfig(objective="logloss", n_trees=30, max_leaves=7),
        reg=GBDTConfig(n_trees=40, max_leaves=15))
    return MaestroPred(cfg).fit(
        [s.obs for s in train],
        np.array([s.true_len for s in train], float),
        np.array([float(s.tool_call) for s in train]))


def live_jobs(jobs: List[traffic_gen.Job], vocab: int, seed: int):
    """The program's ``LiveJob``s for the generated jobs; stage ids are
    global and become the engine request ids."""
    from repro.core.predictor.features import StageObservation
    from repro.serving.cluster import LiveJob, LiveStage
    cache: dict = {}
    out, sid = [], 0
    for ji, job in enumerate(jobs):
        ids = [sid + s.local for s in job.stages]
        sid += len(job.stages)
        stages = [LiveStage(
            stage_id=ids[s.local], job_id=ji,
            deps=[ids[d] for d in s.deps],
            obs=StageObservation(
                app=traffic_gen.APP_ID[job.app],
                role=traffic_gen.ROLE_ID[s.role], position=s.position,
                invocation_idx=s.local, tools_available=s.tools_available,
                cot=s.cot, prompt_len=s.prompt_len, model_id=0,
                text=s.text, src_cluster=job.src_cluster),
            interactive=job.interactive,
            tokens=traffic_gen.prompt_tokens(job, ji, s, vocab, seed,
                                             cache),
            max_new=s.out_len, nominal_len=s.out_len)
            for s in job.stages]
        out.append(LiveJob(job_id=ji, app=job.app,
                           interactive=job.interactive,
                           arrival_s=0.0, stages=stages))
    return out


# ----------------------------------------------------------------- window

@dataclasses.dataclass
class Window:
    """Everything the metric readers get."""
    seconds: float
    cell: Cell
    jobs: list               # LiveJobs offered
    rec: Recorder
    stats0: list             # per node kv_stats() at the window's start
    stats1: list             # ... and at its end
    prefill_pairs: dict      # stage -> (first prompt position run, last)
    compiles: int
    n_nodes: int
    peaks: dict
    trace: Optional[dict] = None
    setup_s: float = 0.0

    # ---- derived views shared by readers
    def stage_table(self):
        """Per stage: (due, first token, finish, submit), clipped to the
        window; None where it did not happen in it."""
        T = self.seconds
        rec = self.rec
        fin = {s: t for s, t in rec.finish_t.items() if t <= T}
        rows = {}
        for job in self.jobs:
            for st in job.stages:
                if st.deps:
                    if not all(d in fin for d in st.deps):
                        continue
                    due = max(fin[d] for d in st.deps)
                else:
                    due = job.arrival_s
                if due >= T:
                    continue
                ft = rec.first_t.get(st.stage_id)
                sub = rec.submit_t.get(st.stage_id)
                rows[st.stage_id] = (
                    due, ft if ft is not None and ft <= T else None,
                    fin.get(st.stage_id),
                    sub if sub is not None and sub <= T else None)
        return rows


def percentile(xs, q: float) -> Optional[float]:
    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, float), q))


def serve_window(fleet: Fleet, cell: Cell, jobs, predictor, rec: Recorder,
                 seconds: float, annotate, compile_count) -> Window:
    from repro.serving.gateway import ClusterGateway, GatewayConfig
    rtt = np.asarray(cell.mix["rtt_s"], float)
    gw = ClusterGateway(fleet.nodes, rtt, predictor=predictor,
                        policy=PINS["policy"],
                        cfg=GatewayConfig(clock="wall",
                                          node_backend="inproc"))
    rec.clock = gw.clock
    stats0 = [n.kv_stats() for n in fleet.nodes]
    c0 = compile_count()
    rec.on = True
    with annotate("bench:window"):
        gw.run(jobs, max_run_s=seconds)
    rec.on = False
    compiles = compile_count() - c0
    stats1 = [n.kv_stats() for n in fleet.nodes]
    cursor = {}
    for n in fleet.nodes:
        cursor.update(n.engines[fleet.name]._prefill_pos)
    pairs = {rid: (int(req.prefill_avoided),
                   int(cursor.get(rid, len(req.tokens))))
             for rid, req in rec.reqs.items()}
    return Window(seconds=seconds, cell=cell, jobs=jobs, rec=rec,
                  stats0=stats0, stats1=stats1, prefill_pairs=pairs,
                  compiles=compiles, n_nodes=len(fleet.nodes), peaks={})


def release(fleet: Fleet) -> None:
    """Free every node's KV and arena (the weights stay, for the
    reference). The arena's planes are deleted outright, whatever may still
    refer to them, so that the reference finds their memory free."""
    for n in fleet.nodes:
        for e in n.engines.values():
            e.release_kv()
            e.params = None
        for plane in n.arena.planes.values():
            plane.k.delete()
            plane.v.delete()
        n.engines.clear()
        n.device_params.clear()
        n.arena = None
    fleet.nodes = []
    gc.collect()


# --------------------------------------------------------------- one run

def compile_counter():
    """A function that returns how many programs JAX has compiled or loaded
    from its cache since this call."""
    import jax
    n = [0]

    def count(event, secs, **kw):
        if event in COMPILE_EVENTS:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    return lambda: n[0]


@dataclasses.dataclass
class Served:
    """One served window, with what the comparison reads once the program's
    state is freed."""
    window: Window
    weights: object          # the seed's weights (one chip's copy)
    items: list              # the seed's sample: (prompt, served tokens)
    memory_peak_bytes: int


def serve_once(cell: Cell, seed: int, seconds: float, devices, compiles,
               t_start: float, predictor=None,
               trace_dir: Optional[Path] = None,
               root: Path = ROOT) -> Served:
    """Everything a run does but judge: build and warm the fleet, train the
    policy's predictor (unless given one), make the seed's jobs; then serve
    one window, traced where ``trace_dir`` is given; read the chips' memory
    peak, free the fleet and draw the seed's sample of finished stages.
    ``setup_s`` runs from ``t_start`` to the window's opening."""
    import jax
    annotate = (jax.profiler.TraceAnnotation if trace_dir is not None
                else (lambda name: contextlib.nullcontext()))
    rec = Recorder()
    fleet = build_fleet(cell, seed, devices, rec, annotate)
    warm(fleet, cell, team_prefixes(cell, seed))
    predictor = predictor or train_predictor()
    live = live_jobs(traffic_gen.generate(cell.mix, seed),
                     int(cell.config["vocab_size"]), seed)
    setup_s = time.perf_counter() - t_start
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    w = serve_window(fleet, cell, live, predictor, rec, seconds, annotate,
                     compiles)
    w.setup_s = setup_s
    if trace_dir is not None:
        jax.profiler.stop_trace()
        prof = jax.profiler.ProfileData.from_file(
            trace_red.latest_xplane(str(trace_dir)))
        w.trace = trace_red.reduce(
            trace_red.extract(prof),
            trace_red.kernel_names(root / "bench" / "kernels"))
        del prof
        shutil.rmtree(trace_dir, ignore_errors=True)
    chips = devices[:cell.chips]
    peak = (max(d.memory_stats()["peak_bytes_in_use"] for d in chips)
            if chips[0].platform != "cpu" else 0)
    weights = fleet.weights[0]
    release(fleet)
    prompts = {s.stage_id: s.tokens for j in live for s in j.stages}
    finished = {sid: (prompts[sid], out) for sid, out in rec.out.items()
                if rec.finish_t[sid] <= seconds and sid not in rec.truncated
                and out}
    picked = check.sample(finished, rec.node_of, seed,
                          int(cell.config["check"]["sample_requests"]))
    return Served(window=w, weights=weights,
                  items=[finished[s] for s in picked],
                  memory_peak_bytes=int(peak))
