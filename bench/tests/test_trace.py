"""The trace reduction, on a hand-made trace and on a slice of a trace
recorded on a TPU v5e (``data/trace_v5e.json``, made by ``trace.extract``)."""
import json
from pathlib import Path

import pytest

from bench import trace

KERNELS = {"paged_attention": ("paged_attention",),
           "chunk_prefill": ("chunk_prefill_attention",)}


def test_busy_idle_and_kernels_by_hand():
    events = {
        "spans": [("window", 0.0, 1.0), ("node.step", 0.05, 0.5)],
        "chips": {"/device:TPU:0": [
            (0.1, 0.3, "paged_attention bf16[16,32,128]"),
            (0.2, 0.4, "chunk_prefill_attention bf16[16,8,128,1024]"),
            (0.2, 0.6, "while"),
            (0.6, 0.7, "paged_attention bf16[16,32,128]"),
            (1.2, 1.3, "fusion bf16[4]")]}}
    r = trace.reduce(events, KERNELS)
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx(0.6)
    assert r["chips"][0]["idle_share"] == pytest.approx(0.4)
    assert r["kernel_s"]["paged_attention"] == pytest.approx(0.3)
    assert r["kernel_s"]["chunk_prefill"] == pytest.approx(0.2)
    assert r["kernel_calls"] == {"paged_attention": 2, "chunk_prefill": 1}
    gaps = sorted(d for _, d in r["idle_gaps"])
    assert gaps == pytest.approx([0.1, 0.3])
    assert r["idle_by_host_span"] == {"gateway": pytest.approx(0.4)}
    assert [k for k, _ in r["device_ops"]] == [
        "paged_attention bf16[16,32,128]",
        "chunk_prefill_attention bf16[16,8,128,1024]"]


def test_op_labels_from_hlo_text():
    assert trace.op_label("%fusion.143 = bf16[16,256,12288]{2,1,0:T(8,128)"
                          "(2,1)} fusion(bf16[24,4096,12288]{2,1,0} %g)") \
        == "fusion bf16[16,256,12288]"
    assert trace.op_label("%while.58 = (s32[]{:T(128)}, s32[16,8]) while("
                          "%t)") == "while"
    assert trace.op_label("%dynamic-slice_bitcast_fusion.5.remat = bf16[2048"
                          ",16,8,128]{3,2,1,0} fusion(%a)") == \
        "dynamic-slice_bitcast_fusion bf16[2048,16,8,128]"
    assert trace.op_label("%paged_attention.5 = bf16[16,32,128]{2,1,0} "
                          "custom-call(%a)") == "paged_attention bf16[16,32,128]"


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


RECORDED = Path(__file__).resolve().parent / "data" / "trace_v5e.json"


def _busy_by_sweep(ops, w0, w1):
    """Busy time by an event sweep (an independent count of the union)."""
    edges = sorted([(max(a, w0), 1) for a, b, _ in ops if b > w0 and a < w1]
                   + [(min(b, w1), -1) for a, b, _ in ops
                      if b > w0 and a < w1])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_v5e_trace():
    events = json.loads(RECORDED.read_text())
    kernels = trace.kernel_names(Path(__file__).resolve().parents[1]
                                 / "kernels")
    r = trace.reduce(events, kernels)
    ops = events["chips"]["/device:TPU:0"]
    w0, w1 = events["spans"][0][1], events["spans"][0][2]
    assert r["window_s"] == pytest.approx(w1 - w0)
    assert r["busy_s"] == pytest.approx(_busy_by_sweep(ops, w0, w1))
    assert 0.0 < r["busy_s"] <= r["window_s"]
    paged = [b - a for a, b, lab in ops if lab.startswith("paged_attention ")]
    assert r["kernel_calls"]["paged_attention"] == len(paged) > 0
    assert r["kernel_s"]["paged_attention"] == pytest.approx(sum(paged))
    idle = sum(d for _, d in r["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-12
