"""The reader of ``node_step_overlap`` on hand-made windows of two nodes."""
import types

import pytest

from bench import run as R
from bench.tests.conftest import ROOT


def window(stats0, stats1, seconds=10.0):
    return types.SimpleNamespace(trace=None, seconds=seconds, stats0=stats0,
                                 stats1=stats1)


def node(step_s=None):
    st = {"engine_decode_tokens": 0}
    if step_s is not None:
        st["node_step_s"] = step_s
    return st


def test_nothing_to_read_without_the_counter():
    w = window([node(), node()], [node(), node()])
    assert R.read_metric(ROOT, "node_step_overlap", w) is None


def test_two_nodes_each_stepping_half_the_window():
    # deltas 5 and 5 seconds of a 10-second window: one node at a time
    w = window([node(2.0), node(0.0)], [node(7.0), node(5.0)])
    assert R.read_metric(ROOT, "node_step_overlap", w) == pytest.approx(1.0)


def test_two_nodes_each_stepping_the_whole_window():
    w = window([node(1.0), node(3.0)], [node(11.0), node(13.0)])
    assert R.read_metric(ROOT, "node_step_overlap", w) == pytest.approx(2.0)
