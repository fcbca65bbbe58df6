"""Multi-process node backend: worker protocol + inproc/process parity.

The acceptance bar for the process backend is exact reproducibility: under
the gateway's deterministic virtual clock, a fleet of worker processes must
produce the SAME completion sets and the SAME metrics as the cooperative
in-process fleet — concurrency changes wall-clock, never the outcome."""
import multiprocessing as mp

import numpy as np
import pytest

from _stubs import StubPred
from repro.data.tracegen import generate_trace
from repro.serving.cluster import (ClusterSpec, LiveJob, LiveStage, NodeSpec,
                                   build_fleet, jobs_from_trace)
from repro.serving.engine import PromptTooLongError, Request
from repro.serving.gateway import ClusterGateway, GatewayConfig
from repro.serving.worker import (NodeHandle, WorkerSpec, close_fleet,
                                  spawn_fleet)

RTT = np.array([[0.001, 0.04], [0.04, 0.001]])
ZOO_NAMES = ("qwen3-8b",)

# GatewayMetrics fields that legitimately differ between backends: the
# backend tag itself, the wall-clock/IPC accounting of the workers, the
# socket transport's byte counters (zero on pipe backends), and the
# engine-measured wall TTFT percentiles (real elapsed time, not virtual)
BACKEND_ONLY = {"node_backend", "ipc_calls", "ipc_wall_s",
                "worker_step_wall_s", "worker_stats",
                "rpc_bytes_sent", "rpc_bytes_recv",
                "ttft_p50_s", "ttft_p95_s"}


def _run(backend, make_jobs, specs, policy="fcfs", predictor=None):
    spec = ClusterSpec(nodes=tuple(specs), rtt_s=RTT, model_names=ZOO_NAMES)
    fleet = build_fleet(spec, backend=backend)
    try:
        gw = ClusterGateway(fleet, RTT, predictor=predictor, policy=policy,
                            cfg=GatewayConfig(node_backend=backend))
        m = gw.run(make_jobs())
        events = {sid: (e.node_id, e.out_len, e.finish_t, e.dispatch_t,
                        e.preemptions, e.queue_delay_s)
                  for sid, e in gw.telemetry.events.items()}
    finally:
        close_fleet(fleet)       # covers gateway-constructor failures too
    return m, events


def _assert_parity(m_in, ev_in, m_proc, ev_proc):
    assert set(ev_in) == set(ev_proc)              # same completion set
    assert ev_in == ev_proc                        # same nodes/times/outputs
    row_in, row_proc = m_in.row(), m_proc.row()
    for k in row_in:
        if k not in BACKEND_ONLY:
            assert row_in[k] == row_proc[k], (k, row_in[k], row_proc[k])


def test_trace_workload_parity():
    """Generated multi-agent trace over two clusters: identical completion
    sets and bit-identical metrics on inproc vs worker-process fleets, and
    the workers really did the serving (per-node IPC counters > 0)."""
    specs = [NodeSpec(0, max_slots=2), NodeSpec(1, max_slots=2)]

    def jobs():
        return jobs_from_trace(generate_trace(3, rate=2.0, seed=5),
                               n_clusters=2, prompt_cap=8, gen_cap=8, seed=2)

    m_in, ev_in = _run("inproc", jobs, specs)
    m_proc, ev_proc = _run("process", jobs, specs)
    assert m_in.finished_jobs == 3 and m_in.node_backend == "inproc"
    assert m_proc.node_backend == "process"
    _assert_parity(m_in, ev_in, m_proc, ev_proc)
    assert m_proc.ipc_calls > 0 and m_proc.ipc_wall_s > 0
    assert set(m_proc.worker_stats) == {0, 1}
    for stats in m_proc.worker_stats.values():     # every node saw traffic
        assert stats["ipc_calls"] > 0
        assert stats["worker_step_wall_s"] > 0
    assert m_in.ipc_calls == 0 and not m_in.worker_stats


def test_preemption_parity():
    """Boundary preemption (the path that reads decode progress, which lives
    in the child on the process backend) makes identical decisions."""
    specs = [NodeSpec(0, max_slots=1)]

    def jobs():
        def _obs():
            from repro.core.predictor.features import StageObservation
            return StageObservation(app=0, role=0, position=0.0,
                                    invocation_idx=0, tools_available=0,
                                    cot=False, prompt_len=32, model_id=0,
                                    text="stage", src_cluster=0)
        batch = LiveJob(0, "b", False, 0.0, [
            LiveStage(stage_id=0, job_id=0, deps=[], obs=_obs(),
                      interactive=False, tokens=[1, 2, 3, 4], max_new=40)])
        inter = LiveJob(1, "i", True, 0.3, [
            LiveStage(stage_id=1, job_id=1, deps=[], obs=_obs(),
                      interactive=True, tokens=[5, 6, 7, 8], max_new=5)])
        return [batch, inter]

    m_in, ev_in = _run("inproc", jobs, specs, policy="maestro",
                       predictor=StubPred())
    m_proc, ev_proc = _run("process", jobs, specs, policy="maestro",
                           predictor=StubPred())
    assert m_in.preemptions >= 1                   # the path was exercised
    _assert_parity(m_in, ev_in, m_proc, ev_proc)


def test_worker_handle_protocol():
    """Direct protocol exercise on one spawned worker: signal snapshots,
    admission estimates, typed error propagation, kv stats, idempotent
    shutdown."""
    h = NodeHandle(WorkerSpec(node_id=7, cluster_id=1,
                              model_names=ZOO_NAMES, max_slots=2, s_max=32))
    try:
        h.wait_ready()
        assert set(h.profiles) == set(ZOO_NAMES)
        sig = h.signal()
        assert sig.node_id == 7 and sig.cluster_id == 1
        assert sig.headroom > 0
        assert h.can_admit(1024.0, ZOO_NAMES[0])
        assert h.t_act(ZOO_NAMES[0]) > 0           # cold model
        assert h.degradation_cost(0.0) == 0.0
        with pytest.raises(PromptTooLongError):    # typed, not RuntimeError
            h.submit(ZOO_NAMES[0], Request(req_id=1,
                                           tokens=list(range(40)),
                                           max_new=4))
        h.submit(ZOO_NAMES[0], Request(req_id=2, tokens=[1, 2, 3],
                                       max_new=3))
        out = {}
        for _ in range(20):
            for model, reqs in h.step().items():
                for r in reqs:
                    out[r.req_id] = r
            if out:
                break
        assert out[2].out and len(out[2].out) == 3
        stats = h.kv_stats()
        assert stats["n_engines"] == 1
        assert stats["arena_peak_pages"] > 0
        assert h.worker_stats()["ipc_calls"] == h.ipc_calls > 0
    finally:
        h.close()
        h.close()                                  # second close is a no-op
    assert not h.proc.is_alive()


def test_partial_spawn_failure_leaks_no_workers():
    """If one node of a fleet fails its boot handshake, spawn_fleet tears
    down every already-started worker before raising — a failed spawn
    leaves no orphan processes behind (regression: the old loop started
    workers one by one and abandoned the live ones on the first failure)."""
    before = {p.pid for p in mp.active_children()}
    specs = [WorkerSpec(node_id=0, cluster_id=0, model_names=ZOO_NAMES),
             WorkerSpec(node_id=1, cluster_id=0,
                        model_names=("no-such-model",))]
    with pytest.raises(RuntimeError, match="failed to boot"):
        spawn_fleet(specs)
    leaked = [p for p in mp.active_children()
              if p.pid not in before and p.is_alive()]
    assert not leaked, f"spawn failure leaked workers: {leaked}"


def test_close_fleet_safe_on_half_constructed_handles():
    """close_fleet / handle.close must be callable on handles whose
    constructor never completed (no process, no pipe) and must be
    idempotent — this is the teardown path of a failed spawn."""
    h = NodeHandle.__new__(NodeHandle)
    h._init_state(WorkerSpec(node_id=3, cluster_id=0,
                             model_names=ZOO_NAMES))
    close_fleet([h, object()])     # non-handle members are skipped
    close_fleet([h])               # second close is a no-op


def test_process_backend_requires_worker_fleet(zoo_host=None):
    """Config/fleet mismatch is a construction-time error, not a hang."""
    fleet = build_fleet(ClusterSpec(nodes=(NodeSpec(0),), rtt_s=RTT,
                                    model_names=ZOO_NAMES))
    with pytest.raises(ValueError, match="process"):
        ClusterGateway(fleet, RTT, policy="fcfs",
                       cfg=GatewayConfig(node_backend="process"))
    with pytest.raises(ValueError, match="node_backend"):
        ClusterGateway(fleet, RTT, policy="fcfs",
                       cfg=GatewayConfig(node_backend="threads"))
    with pytest.raises(ValueError, match="backend"):
        build_fleet(ClusterSpec(nodes=(NodeSpec(0),), rtt_s=RTT,
                                model_names=ZOO_NAMES), backend="threads")


def test_local_workers_fail_fast_where_chips_cannot_go_round(monkeypatch):
    """On a TPU host, more local workers than there are chips for them
    raise a typed error before any child starts; CPU runs are untouched."""
    from jax._src import xla_bridge
    from repro.serving import worker
    specs = [WorkerSpec(node_id=i, cluster_id=0, model_names=ZOO_NAMES)
             for i in range(2)]
    # JAX_PLATFORMS=cpu, as tests and CI run: children stay on the CPU
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert worker._host_tpu_chips() == 0
    worker.check_local_chips(8)
    # a host with four chips: every child would claim all of them
    monkeypatch.setattr(worker, "_host_tpu_chips", lambda: 4)
    worker.check_local_chips(1)
    with pytest.raises(worker.ChipUnavailableError, match="2 local workers"):
        spawn_fleet(specs, backend="process")
    with pytest.raises(worker.ChipUnavailableError):
        spawn_fleet(specs, backend="socket")
    assert not mp.active_children()
    # ... and a parent that holds them leaves none for even one child
    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(xla_bridge, "backends", lambda: {"tpu": None})
    with pytest.raises(worker.ChipUnavailableError, match="holds"):
        worker.check_local_chips(1)


def test_worker_that_finds_no_chip_boots_into_a_typed_error():
    """A child on a TPU host that cannot open a chip answers its boot with
    ``no_chip`` instead of serving on the CPU; the handle raises it typed."""
    from repro.serving import worker
    h = NodeHandle.__new__(NodeHandle)
    h._init_state(WorkerSpec(node_id=3, cluster_id=0,
                             model_names=ZOO_NAMES))
    h.proc = None
    h._conn, child = mp.Pipe()
    child.send(("no_chip", "node 3: the chip is held"))
    with pytest.raises(worker.ChipUnavailableError, match="node 3"):
        h.wait_ready()
    child.close()
