"""Percentile edge cases + fault/tail columns in the telemetry plane, the
program's spans and chunk counters, and the engine's decode page growth
under pressure with its counters.

The fleet-summed benchmark paths serialize ``GatewayMetrics.row()`` straight
into BENCH JSON: a NaN (np.percentile of an empty array) or an IndexError
on a single-sample run would poison every downstream comparison, so the
extreme-tail columns (p99/p99.9) are pinned to 0.0 below two samples."""
import collections
import math
import time
import types

import jax
import numpy as np
import pytest

from _stubs import StubPred
from repro.configs import get_config
from repro.core.predictor.features import StageObservation
from repro.core.runtime.accounting import MemoryAccountant
from repro.models import build_model
from repro.serving import telemetry
from repro.serving.cluster import (ClusterSpec, LiveJob, LiveStage, NodeSpec,
                                   build_fleet, build_zoo)
from repro.serving.engine import Engine, Request
from repro.serving.gateway import ClusterGateway
from repro.serving.telemetry import (NodeDeathEvent, Telemetry,
                                     tail_percentile)


def _job(i, stage_ids, interactive=True, arrival=0.0, deadline=10.0):
    return types.SimpleNamespace(
        job_id=i, interactive=interactive, arrival_s=arrival,
        deadline_s=deadline,
        stages=[types.SimpleNamespace(stage_id=s) for s in stage_ids])


def test_tail_percentile_edge_cases():
    assert tail_percentile([], 99) == 0.0
    assert tail_percentile([], 99.9) == 0.0
    assert tail_percentile([3.5], 99) == 0.0          # single sample: noise
    assert tail_percentile([1.0, 2.0], 50) == pytest.approx(1.5)
    xs = [float(i) for i in range(1000)]
    assert tail_percentile(xs, 99.9) > tail_percentile(xs, 99)
    assert not math.isnan(tail_percentile([], 99))


def test_summary_empty_run_has_no_nan():
    m = Telemetry().summary("x", [], {}, 10.0, 0.0)
    assert m.p99_latency_s == 0.0 and m.p999_latency_s == 0.0
    assert m.queue_delay_p99_s == 0.0 and m.queue_delay_p999_s == 0.0
    assert m.stage_latency_p99_s == 0.0 and m.stage_latency_p999_s == 0.0
    assert m.recovery_time_s == 0.0
    assert m.stages_by_model == {} and m.tokens_by_model == {}
    # nothing in the whole row is NaN (json.dumps would emit invalid JSON)
    for k, v in m.row().items():
        if isinstance(v, float):
            assert not math.isnan(v), k


def test_summary_single_sample_run():
    t = Telemetry()
    ev = t.event(0, 0, True)
    ev.ready_t, ev.dispatch_t, ev.start_t, ev.finish_t = 0.0, 0.1, 0.1, 1.0
    m = t.summary("x", [_job(0, [0])], {0: 1.0}, 10.0, 1.0)
    # p95 keeps the observation; the extreme tails refuse to extrapolate
    assert m.p95_latency_s == pytest.approx(1.0)
    assert m.p99_latency_s == 0.0 and m.p999_latency_s == 0.0
    assert m.queue_delay_p999_s == 0.0 and m.stage_latency_p999_s == 0.0


def test_summary_fleet_tails_monotone():
    t = Telemetry()
    jobs, finish = [], {}
    for i in range(200):
        ev = t.event(i, i, True)
        ev.ready_t, ev.dispatch_t = 0.0, 0.002 * i
        ev.start_t, ev.finish_t = 0.002 * i, 0.002 * i + 1.0
        jobs.append(_job(i, [i]))
        finish[i] = ev.finish_t
    m = t.summary("x", jobs, finish, 10.0, 2.0)
    assert m.p95_latency_s <= m.p99_latency_s <= m.p999_latency_s
    assert m.queue_delay_p95_s <= m.queue_delay_p99_s \
        <= m.queue_delay_p999_s
    assert m.stage_latency_p95_s <= m.stage_latency_p99_s \
        <= m.stage_latency_p999_s


def test_recovery_time_from_death_events():
    t = Telemetry()
    for sid, fin in ((0, 4.0), (1, 6.5), (2, 2.0)):
        ev = t.event(sid, sid, False)
        ev.ready_t, ev.finish_t = 0.0, fin
        ev.model = "qwen3-8b" if sid < 2 else "whisper-medium"
        ev.out_len = 10 * (sid + 1)
    # death at t=3 evacuated stages 0 and 1; the last one landed at 6.5
    t.node_death(NodeDeathEvent(node_id=0, t=3.0, cause="test",
                                requeued_stages=(0, 1)))
    jobs = [_job(i, [i], interactive=False, deadline=100.0)
            for i in range(3)]
    m = t.summary("x", jobs, {0: 4.0, 1: 6.5, 2: 2.0}, 10.0, 7.0)
    assert m.recovery_time_s == pytest.approx(3.5)
    assert m.stages_by_model == {"qwen3-8b": 2, "whisper-medium": 1}
    assert m.tokens_by_model == {"qwen3-8b": 30, "whisper-medium": 30}
    # a death whose evacuated stages never finished contributes nothing
    t2 = Telemetry()
    t2.node_death(NodeDeathEvent(node_id=1, t=1.0, cause="test",
                                 requeued_stages=(7,)))
    assert t2.summary("x", [], {}, 10.0, 2.0).recovery_time_s == 0.0


# ------------------------------------------------------------------ spans


class _SpanLog:
    """A recording annotation factory: every span with its args (those
    given at entry and through ``set_metadata``) and its enclosing span."""

    def __init__(self):
        self.spans, self._stack = [], []

    def __call__(self, name, **args):
        log = self

        class _Span:
            def __enter__(self):
                self.rec = {"name": name, "args": dict(args),
                            "parent": log._stack[-1] if log._stack else None}
                log.spans.append(self.rec)
                log._stack.append(self.rec)
                return self

            def __exit__(self, *exc):
                log._stack.pop()
                return False

            def set_metadata(self, **more):
                self.rec["args"].update(more)

        return _Span()


@pytest.fixture
def spans_reset():
    yield
    telemetry.enable_spans(None)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("qwen3-8b").reduced()
    m = build_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _engine_run(tiny_model, lengths, *, chunk=16, horizon=4, max_new=9,
                sequential=False):
    cfg, m, params = tiny_model
    eng = Engine(m, params, MemoryAccountant(m_total=512e6), max_slots=3,
                 s_max=64, kv_backend="ref", prefill_chunk_tokens=chunk,
                 decode_horizon=horizon)
    rng = np.random.default_rng(0)
    reqs = [Request(req_id=i, tokens=[int(t) for t in
                                      rng.integers(0, cfg.vocab, n)],
                    max_new=max_new) for i, n in enumerate(lengths)]
    if sequential:
        for r in reqs:
            eng.submit(r)
            eng.drain()
    else:
        for r in reqs:
            eng.submit(r)
        eng.drain()
    return eng


def test_spans_off_create_no_annotation(tiny_model, spans_reset):
    calls = []

    def counting(name, **args):
        calls.append(name)
        return telemetry.NULL_SPAN

    telemetry.enable_spans(counting)
    telemetry.enable_spans(None)
    assert telemetry.span("engine.step") is telemetry.NULL_SPAN
    assert telemetry.span("node.submit", node=0, req=3) is telemetry.NULL_SPAN
    eng = _engine_run(tiny_model, (5, 20, 40))
    assert eng.stat_chunk_launches > 0 and eng.stat_horizon_steps > 0
    assert calls == []
    # the same run with the factory on passes through it: the check above
    # is not vacuous
    telemetry.enable_spans(counting)
    _engine_run(tiny_model, (5, 20, 40))
    assert {"maestro:engine.chunk", "maestro:engine.horizon.sync"} \
        <= set(calls)


@pytest.mark.parametrize("sequential,launches", [(False, 3), (True, 6)])
def test_chunk_counters_match_hand_counts(tiny_model, sequential, launches):
    # 16-token chunks: 5 -> 1 chunk, 20 -> 2, 40 -> 3. Together, each
    # launch advances every prompt still prefilling (3 launches); one at a
    # time, 1 + 2 + 3
    eng = _engine_run(tiny_model, (5, 20, 40), sequential=sequential)
    assert eng.stat_chunk_launches == launches
    assert eng.stat_chunk_tokens == 5 + 20 + 40


def _ancestors(rec):
    out = []
    while rec["parent"] is not None:
        rec = rec["parent"]
        out.append(rec["name"])
    return out


def test_gateway_step_spans_nest_to_the_chunk_sync(spans_reset):
    zoo, host = build_zoo(("qwen3-8b",), seed=1)
    node, = build_fleet(ClusterSpec(
        nodes=(NodeSpec(0, max_slots=2, prefix_cache=True,
                        prefill_chunk_tokens=16, decode_horizon=4),),
        rtt_s=np.array([[0.001]]), model_names=("qwen3-8b",)),
        zoo=zoo, host=host)
    obs = StageObservation(app=0, role=0, position=0.0, invocation_idx=0,
                           tools_available=0, cot=False, prompt_len=40,
                           model_id=0, text="span test", src_cluster=0)
    prompt = [int(t) for t in np.arange(40) % 97 + 1]
    job = LiveJob(job_id=0, app="t", interactive=True, arrival_s=0.0,
                  stages=[LiveStage(stage_id=s, job_id=0, deps=deps,
                                    obs=obs, interactive=True,
                                    tokens=prompt + [s] * 8, max_new=6)
                          for s, deps in ((0, []), (1, [0]))])
    gw = ClusterGateway([node], np.array([[0.001]]),
                        predictor=StubPred(6.0), policy="maestro")
    log = _SpanLog()
    telemetry.enable_spans(log)
    gw.submit_jobs([job])
    for _ in range(200):
        gw.step()
        if len(gw.done) == 2:
            break
    assert len(gw.done) == 2
    telemetry.enable_spans(None)

    syncs = [s for s in log.spans
             if s["name"] == "maestro:engine.chunk.sync"]
    assert syncs
    for sync in syncs:
        assert _ancestors(sync) == [
            "maestro:engine.chunk", "maestro:engine.step",
            "maestro:node.step", "maestro:gateway.collect",
            "maestro:gateway.step"]
    # every span that acts on one request names it; a stage's spans share
    # the gateway's stage id, from submit through lookup and publish to
    # finish
    by_req = collections.defaultdict(set)
    for s in log.spans:
        if "req" in s["args"]:
            by_req[s["args"]["req"]].add(s["name"])
    for sid in (0, 1):
        assert {"maestro:node.submit", "maestro:engine.prefix_lookup",
                "maestro:engine.publish", "maestro:gateway.finish"} \
            <= by_req[sid]
    assert set(by_req) == {0, 1}
    chunks = [s for s in log.spans if s["name"] == "maestro:engine.chunk"]
    assert sum(s["args"]["tokens"] for s in chunks) \
        == node.kv_stats()["engine_chunk_tokens"] > 0
    assert len(chunks) == node.kv_stats()["engine_chunk_launches"]


# ------------------------------------------- decode page growth under pressure


def _squeezed(tiny_model, reqs, *, squeeze, horizon=1, prefix_cache=False,
              cached=()):
    """Serve ``reqs`` (prompt, max_new, pred_len) on 3 slots, in chunks of
    16. ``cached`` prompts are served first, so that the prefix index holds
    their pages and nothing else does. With ``squeeze``, after the step
    that admits ``reqs`` and runs their first chunk (before any decodes)
    the accountant has no headroom left: a lane that outgrows its
    admission grant gets a page only from memory something else gives up."""
    cfg, m, params = tiny_model
    acc = MemoryAccountant(m_total=512e6)
    eng = Engine(m, params, acc, max_slots=3, s_max=64, kv_backend="ref",
                 prefix_cache=prefix_cache, prefill_chunk_tokens=16,
                 decode_horizon=horizon)
    for i, p in enumerate(cached):
        eng.submit(Request(req_id=100 + i, tokens=list(p), max_new=1))
    eng.drain()
    for i, (p, n, pred) in enumerate(reqs):
        eng.submit(Request(req_id=i, tokens=list(p), max_new=n,
                           pred_len=pred))
    done = {r.req_id: r for r in eng.step()}
    assert not eng.waiting
    if squeeze:
        acc.m_other += acc.headroom
    done.update({r.req_id: r for r in eng.drain()})
    assert eng.arena.check_mirror()
    return eng, done


def _tokens(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 97, n)]


@pytest.mark.parametrize("horizon", [1, 8])
def test_decode_growth_trims_pins_before_any_lane_waits(tiny_model,
                                                        horizon):
    """The prefix index holds 12 pages no sequence uses and the accountant
    nothing more: each lane that outgrows its grant takes a pinned page,
    so none waits and none is truncated."""
    reqs = [(_tokens(20, 1), 24, 1), (_tokens(21, 2), 24, 1)]
    cached = [_tokens(48, 10 + i) for i in range(4)]
    _, free = _squeezed(tiny_model, reqs, squeeze=False, horizon=horizon)
    eng, got = _squeezed(tiny_model, reqs, squeeze=True, horizon=horizon,
                         prefix_cache=True, cached=cached)
    assert {k: r.out for k, r in got.items()} == \
        {k: r.out for k, r in free.items()}
    assert not any(r.truncated for r in got.values())
    assert eng.stat_pin_trims > 0
    assert eng.stat_grow_waits == 0 and eng.stat_preemptions == 0


@pytest.mark.parametrize("horizon", [1, 8])
def test_blocked_lane_waits_for_another_to_finish(tiny_model, horizon):
    """Lane 1 outgrows its grant at token 16 while lane 0, whose grant
    covers its whole output, still decodes: lane 1 waits until lane 0's
    pages come back, then ends as it ends unpressured."""
    reqs = [(_tokens(3, 3), 12, 12), (_tokens(12, 4), 20, 1)]
    _, free = _squeezed(tiny_model, reqs, squeeze=False, horizon=horizon)
    eng, got = _squeezed(tiny_model, reqs, squeeze=True, horizon=horizon)
    assert {k: r.out for k, r in got.items()} == \
        {k: r.out for k, r in free.items()}
    assert not any(r.truncated for r in got.values())
    assert eng.stat_grow_waits > 0 and eng.stat_preemptions == 0


@pytest.mark.parametrize("horizon", [1, 8])
def test_stalled_engine_preempts_its_latest_lane(tiny_model, horizon):
    """Both lanes outgrow their grants at token 32 together and nothing
    else can give a page: the lane admitted last is preempted, the other
    takes its pages at once and finishes, and the preempted stage restarts
    and ends as it ends unpressured."""
    reqs = [(_tokens(15, 5), 24, 1), (_tokens(15, 6), 24, 1)]
    _, free = _squeezed(tiny_model, reqs, squeeze=False, horizon=horizon)
    eng, got = _squeezed(tiny_model, reqs, squeeze=True, horizon=horizon)
    assert {k: r.out for k, r in got.items()} == \
        {k: r.out for k, r in free.items()}
    assert not any(r.truncated for r in got.values())
    assert eng.stat_preemptions == 1


def test_growth_counters_in_node_kv_stats(tiny_model):
    from repro.serving.node_runtime import NodeRuntime
    cfg, m, params = tiny_model
    host = jax.tree.map(np.asarray, params)
    node = NodeRuntime(0, 0, {cfg.name: m}, {cfg.name: host},
                       hbm_budget=1.2e9, max_slots=3, s_max=64,
                       prefill_chunk_tokens=16)
    node.submit(cfg.name, Request(req_id=0, tokens=_tokens(20, 7),
                                  max_new=4))
    for _ in range(30):
        node.step()
        if not node.has_work():
            break
    eng = node.engines[cfg.name]
    eng.stat_pin_trims, eng.stat_grow_waits, eng.stat_preemptions = 3, 2, 1
    st = node.kv_stats()
    # two launches of one lane each; the counters are the engines' sums
    assert st["engine_chunk_launches"] == 2
    assert st["engine_chunk_lanes"] == 2
    assert (st["engine_pin_trims"], st["engine_grow_waits"],
            st["engine_preemptions"]) == (3, 2, 1)


def test_step_seconds_in_node_kv_stats(tiny_model):
    """``node_step_s`` is the wall time spent inside ``NodeRuntime.step``:
    zero before the first step, then growing with each step, and never
    more than the time around the steps."""
    from repro.serving.node_runtime import NodeRuntime
    cfg, m, params = tiny_model
    host = jax.tree.map(np.asarray, params)
    node = NodeRuntime(0, 0, {cfg.name: m}, {cfg.name: host},
                       hbm_budget=1.2e9, max_slots=3, s_max=64,
                       prefill_chunk_tokens=16)
    assert node.kv_stats()["node_step_s"] == 0.0
    node.submit(cfg.name, Request(req_id=0, tokens=_tokens(20, 7),
                                  max_new=4))
    seen, outside = [], 0.0
    for _ in range(30):
        t0 = time.perf_counter()
        node.step()
        outside += time.perf_counter() - t0
        seen.append(node.kv_stats()["node_step_s"])
        if not node.has_work():
            break
    assert all(b > a for a, b in zip([0.0] + seen, seen))
    assert 0 < seen[-1] <= outside


# ------------------------------------------------------- routing counters


def _four_node_run(log=None):
    """Six three-stage chains from clusters 0-2 through the maestro policy
    onto four nodes in clusters 0, 0, 1, 2, on the virtual clock; with
    ``log``, spans on."""
    from repro.core.topology import DEFAULT_RTT
    zoo, host = build_zoo(("qwen3-8b",), seed=1)
    nodes = build_fleet(ClusterSpec(
        nodes=tuple(NodeSpec(c, max_slots=2, prefix_cache=True,
                             prefill_chunk_tokens=16, decode_horizon=4)
                    for c in (0, 0, 1, 2)),
        rtt_s=DEFAULT_RTT, model_names=("qwen3-8b",)), zoo=zoo, host=host)
    jobs, sid = [], 0
    for j in range(6):
        obs = StageObservation(app=0, role=0, position=0.0, invocation_idx=0,
                               tools_available=0, cot=False, prompt_len=40,
                               model_id=0, text="route test",
                               src_cluster=j % 3)
        prompt = [int(t) for t in (np.arange(24) * (j + 3)) % 97 + 1]
        stages = []
        for k in range(3):
            prompt = prompt + [sid % 89 + 1] * 8
            stages.append(LiveStage(stage_id=sid, job_id=j,
                                    deps=[sid - 1] if k else [], obs=obs,
                                    interactive=True, tokens=list(prompt),
                                    max_new=4))
            sid += 1
        jobs.append(LiveJob(job_id=j, app="t", interactive=True,
                            arrival_s=0.0, stages=stages))
    gw = ClusterGateway(nodes, DEFAULT_RTT, predictor=StubPred(4.0),
                        policy="maestro")
    telemetry.enable_spans(log)
    gw.submit_jobs(jobs)
    for _ in range(600):
        gw.step()
        if len(gw.done) == sid:
            break
    telemetry.enable_spans(None)
    assert len(gw.done) == sid
    assert all(ev.preemptions == 0 for ev in gw.telemetry.events.values())
    return gw, nodes, [s for job in jobs for s in job.stages]


def test_child_counters_match_the_gateways_placements(spans_reset):
    gw, nodes, stages = _four_node_run()
    stats = [n.kv_stats() for n in nodes]
    children = [s for s in stages if s.deps]
    # every child stage was placed once, on the node its counter names
    assert sum(st["node_child_stages"] for st in stats) == len(children)
    for n, st in zip(nodes, stats):
        mine = [s for s in children if gw.placed_on[s.stage_id] == n.node_id]
        assert st["node_child_stages"] == len(mine)
        assert st["node_child_affine"] == sum(
            gw.placed_on[s.deps[0]] == n.node_id for s in mine)
    affine = sum(st["node_child_affine"] for st in stats)
    assert 0 < affine < len(children)      # both kinds occur in this run


def test_route_span_carries_its_placement(spans_reset):
    from repro.core.topology import DEFAULT_RTT
    assert telemetry.span("gateway.route", req=0, src=0, node=0, rtt_ms=0.5,
                          affine=False) is telemetry.NULL_SPAN
    log = _SpanLog()
    gw, nodes, stages = _four_node_run(log)
    routes = [s for s in log.spans if s["name"] == "maestro:gateway.route"]
    assert len(routes) == len(stages)          # one per placement
    by_id = {s.stage_id: s for s in stages}
    for r in routes:
        assert r["parent"]["name"] == "maestro:gateway.dispatch"
        a = r["args"]
        assert set(a) == {"req", "src", "node", "rtt_ms", "affine"}
        stage = by_id[a["req"]]
        assert a["node"] == gw.placed_on[stage.stage_id]
        assert a["src"] == stage.obs.src_cluster
        assert a["rtt_ms"] == pytest.approx(
            1e3 * DEFAULT_RTT[a["src"], nodes[a["node"]].cluster_id])
        assert a["affine"] == (bool(stage.deps)
                               and gw.placed_on[stage.deps[0]] == a["node"])
