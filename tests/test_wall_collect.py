"""The wall-clock gateway's collect phase over in-process nodes, with fake
nodes whose steps run no JAX program: two or more busy nodes step at once on
their own threads, one busy node steps on the gateway's thread, outputs
drain in fleet order, a step's exception reaches the caller, and the
threads are the wall clock's alone. Also: threads that share a model share
its jitted wrappers."""
import sys
import threading
import time
import types

import numpy as np
import pytest

from repro.serving.engine import _model_jit
from repro.serving.gateway import ClusterGateway, GatewayConfig


class FakeNode:
    """What the gateway reads of an in-process node, with a step given by
    the test."""

    def __init__(self, node_id, work=True, step=None):
        self.node_id = node_id
        self.cluster_id = 0
        self.profiles = {}
        self.max_slots = 1
        self.s_max = 64
        self.page_tokens = 16
        self.acc = types.SimpleNamespace(headroom=0.0)
        self.work = work
        self._step = step or (lambda: {})
        self.threads = []

    def has_work(self):
        return self.work

    def step(self):
        self.threads.append(threading.current_thread())
        return self._step()


def gateway(nodes, clock="wall"):
    rtt = np.full((1, 1), 0.001)
    return ClusterGateway(nodes, rtt, policy="fcfs",
                          cfg=GatewayConfig(clock=clock,
                                            node_backend="inproc"))


def test_busy_nodes_step_concurrently():
    # each step waits for all four: stepped in turn, the first would time
    # out and break the barrier
    barrier = threading.Barrier(4, timeout=5)

    def step():
        barrier.wait()
        return {}

    nodes = [FakeNode(i, step=step) for i in range(4)]
    gw = gateway(nodes)
    try:
        gw.step()
        gw.step()
    finally:
        gw.close()
    for n in nodes:
        assert len(n.threads) == 2
        assert threading.main_thread() not in n.threads
    assert not barrier.broken


def test_one_busy_node_steps_on_the_gateway_thread():
    nodes = [FakeNode(0, work=False), FakeNode(1), FakeNode(2, work=False)]
    gw = gateway(nodes)
    gw.step()
    assert nodes[1].threads == [threading.current_thread()]
    assert nodes[0].threads == nodes[2].threads == []
    assert gw._step_pool is None
    assert gw._node_busy_s[1] > 0 and gw._node_busy_s[0] == 0


def test_outputs_drain_in_fleet_order():
    # node 0 sleeps longest, so the threads finish in reverse fleet order
    def step_of(i):
        def step():
            time.sleep(0.05 * (3 - i))
            return {"m": [i]}
        return step

    nodes = [FakeNode(i, step=step_of(i)) for i in range(4)]
    gw = gateway(nodes)
    drained = []
    gw._drain = lambda out, now: drained.append(out["m"][0])
    try:
        gw.step()
    finally:
        gw.close()
    assert drained == [0, 1, 2, 3]
    # each node's busy seconds are its own step's, timed in its thread
    assert gw._node_busy_s[0] > gw._node_busy_s[3]


def test_a_step_exception_reaches_the_caller():
    def boom():
        raise RuntimeError("node 2 failed")

    nodes = [FakeNode(0), FakeNode(1), FakeNode(2, step=boom), FakeNode(3)]
    gw = gateway(nodes)
    drained = []
    gw._drain = lambda out, now: drained.append(out)
    try:
        with pytest.raises(RuntimeError, match="node 2 failed"):
            gw.step()
    finally:
        gw.close()
    # the nodes before it in fleet order drained; all four stepped
    assert len(drained) == 2
    assert all(len(n.threads) == 1 for n in nodes)


def test_close_shuts_the_pool_down():
    nodes = [FakeNode(i) for i in range(3)]
    gw = gateway(nodes)
    gw.step()
    pool = gw._step_pool
    assert pool is not None
    gw.close()
    assert gw._step_pool is None
    with pytest.raises(RuntimeError):
        pool.submit(lambda: None)          # shut down: takes no more work


def test_virtual_clock_steps_on_the_gateway_thread():
    nodes = [FakeNode(i) for i in range(3)]
    gw = gateway(nodes, clock="virtual")
    for _ in range(3):
        gw.step()
    gw.close()
    assert gw._step_pool is None
    for n in nodes:
        assert n.threads == [threading.current_thread()] * 3


def test_threads_share_one_jitted_wrapper():
    # sixteen threads ask at once for the same wrapper of a fresh model,
    # each building its own slowly: all must get the one stored first
    model = types.SimpleNamespace()
    barrier = threading.Barrier(16, timeout=5)
    got = []

    def builder():
        time.sleep(0.001)
        return object()

    def ask():
        barrier.wait()
        got.append(_model_jit(model, ("decode",), builder))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(got) == 16 and len({id(fn) for fn in got}) == 1
    assert model._engine_jit_cache == {("decode",): got[0]}
