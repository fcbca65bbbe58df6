"""The readers of ``route_token_imbalance`` and ``route_affinity_share`` on
hand-made windows of four nodes."""
import types

import pytest

from bench import run as R
from bench.tests.conftest import ROOT


def window(stats0, stats1):
    return types.SimpleNamespace(trace=None, stats0=stats0, stats1=stats1)


def node(decode, stages=None, affine=None):
    st = {"engine_decode_tokens": decode}
    if stages is not None:
        st.update(node_child_stages=stages, node_child_affine=affine)
    return st


def test_busiest_node_over_the_mean():
    # window deltas 300, 100, 200, 200: mean 200, busiest 300
    w = window([node(50), node(0), node(10), node(0)],
               [node(350), node(100), node(210), node(200)])
    assert R.read_metric(ROOT, "route_token_imbalance", w) == \
        pytest.approx(1.5)


def test_affine_children_over_children():
    # deltas: 4 of 10, 0 of 2, 3 of 3, 1 of 5 -> 8 of 20
    w = window([node(0, 5, 1), node(0, 0, 0), node(0, 1, 1), node(0, 2, 2)],
               [node(9, 15, 5), node(9, 2, 0), node(9, 4, 4),
                node(9, 7, 3)])
    assert R.read_metric(ROOT, "route_affinity_share", w) == \
        pytest.approx(40.0)


def test_nothing_to_read():
    # no decode in the window; a program without the routing counters; no
    # child stage placed in the window
    idle = window([node(7)] * 4, [node(7)] * 4)
    old = window([node(0)] * 4, [node(5)] * 4)
    none = window([node(0, 3, 1)] * 4, [node(5, 3, 1)] * 4)
    assert R.read_metric(ROOT, "route_token_imbalance", idle) is None
    assert R.read_metric(ROOT, "route_affinity_share", old) is None
    assert R.read_metric(ROOT, "route_affinity_share", none) is None
