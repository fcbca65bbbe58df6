"""The reader of ``chunk_row_fill_share`` on hand-made windows."""
import types

import pytest

from bench import run as R
from bench.tests.conftest import ROOT


def window(stats0, stats1):
    return types.SimpleNamespace(
        trace=None, stats0=stats0, stats1=stats1,
        cell=types.SimpleNamespace(serving={"max_slots": 16,
                                            "prefill_chunk_tokens": 256}))


def test_tokens_over_the_lanes_launched():
    # two nodes; 2048 + 1024 prompt tokens in 12 + 4 launched lanes of 256
    w = window([{"engine_chunk_lanes": 10, "engine_chunk_tokens": 1000},
                {"engine_chunk_lanes": 0, "engine_chunk_tokens": 0}],
               [{"engine_chunk_lanes": 22, "engine_chunk_tokens": 3048},
                {"engine_chunk_lanes": 4, "engine_chunk_tokens": 1024}])
    assert R.read_metric(ROOT, "chunk_row_fill_share", w) == \
        pytest.approx(100 * 3072 / (16 * 256))


def test_nothing_to_read():
    # a program without the lane counter, and a window with no launch
    old = window([{"engine_chunk_launches": 1, "engine_chunk_tokens": 9}],
                 [{"engine_chunk_launches": 2, "engine_chunk_tokens": 99}])
    idle = window([{"engine_chunk_lanes": 5, "engine_chunk_tokens": 9}],
                  [{"engine_chunk_lanes": 5, "engine_chunk_tokens": 9}])
    for w in (old, idle):
        assert R.read_metric(ROOT, "chunk_row_fill_share", w) is None
