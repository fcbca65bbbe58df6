"""Idle time by the program's spans (``bench/spans.py``) on hand-made
traces, and the readers of the metrics that need the spans or the chunk
counters, on hand-made windows; a slice of a window recorded on a TPU v5e
with the program's spans on (``data/trace_v5e_spans.json``, cut from
``bench/spans.py --events``); and the whole run on the CPU at test widths
with the program's spans on."""
import json
import types
from pathlib import Path

import pytest

from bench import harness as H, run as R
from bench import spans, trace
from bench.tests.test_rehearsal import cpu_run

ROOT = Path(__file__).resolve().parents[2]
KERNELS = {"paged_attention": ("paged_attention",),
           "chunk_prefill": ("chunk_prefill_attention",)}


def nested_events():
    """A 10 s window: a gateway step holding a collect, the harness's node
    step, the program's node and engine steps and a chunk launch with its
    sync; the harness's recording after the step; the gateway's sleep; a
    gateway step that runs past the window. Idle: [0.5, 3.5], [5, 6],
    [7.1, 9.5]."""
    return {
        "spans": [("window", 0.0, 10.0), ("node.step", 2.1, 7.0),
                  ("record", 7.2, 7.8)],
        "program_spans": [
            ("gateway.step", 1.0, 9.0), ("gateway.collect", 2.0, 8.0),
            ("node.step", 2.2, 6.9), ("engine.step", 2.3, 6.8),
            ("engine.chunk", 2.4, 5.0), ("engine.chunk.sync", 3.0, 5.0),
            ("gateway.sleep", 8.5, 9.0), ("gateway.step", 9.8, 10.5)],
        "chips": {"/device:TPU:0": [
            (0.0, 0.5, "fusion bf16[4]"),
            (3.5, 5.0, "chunk_prefill_attention bf16[16,8,128,1024]"),
            (6.0, 7.1, "paged_attention bf16[16,32,128]"),
            (9.5, 10.0, "fusion bf16[4]")]}}


def test_idle_goes_to_the_innermost_span():
    a = spans.reduce(nested_events(), KERNELS)
    idle = a["idle_by_span"]
    # [0.5, 3.5] is cut at six span edges; [7.1, 9.5] runs from the
    # collect through the harness's recording, the collect again, the
    # step, the sleep, and out of every span
    assert idle == pytest.approx({
        "unattributed": 0.5 + 0.5, "gateway.step": 1.0 + 0.5,
        "gateway.collect": 0.1 + 0.1 + 0.2, "bench:node.step": 0.1,
        "node.step": 0.1, "engine.step": 0.1 + 1.0, "engine.chunk": 0.6,
        "engine.chunk.sync": 0.5, "bench:record": 0.6,
        "gateway.sleep": 0.5})
    r = trace.reduce(nested_events(), KERNELS)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # everything else as the harness's reduction reads it
    assert {k: v for k, v in a.items() if k not in (
        "idle_gaps", "idle_by_span", "span_durations")} == {
        k: v for k, v in r.items() if k != "idle_gaps"}
    # each gap after the span holding most of it
    assert a["idle_gaps"] == [["gateway.step gap", pytest.approx(3.0)],
                              ["bench:record gap", pytest.approx(2.4)],
                              ["engine.step gap", pytest.approx(1.0)]]
    # durations of the spans wholly inside the window only
    d = a["span_durations"]
    assert d["gateway.step"] == pytest.approx([8.0])
    assert d["engine.chunk"] == pytest.approx([2.6])
    assert d["bench:record"] == pytest.approx([0.6])
    assert "window" not in d and "bench:window" not in d


def test_program_spans_leave_the_reduction_as_it_was():
    ev = nested_events()
    bare = dict(ev)
    del bare["program_spans"]
    assert trace.reduce(ev, KERNELS) == trace.reduce(bare, KERNELS)


def test_without_program_spans_gaps_keep_the_harness_names():
    ev = {"spans": [("window", 0.0, 6.0), ("node.step", 1.0, 3.0)],
          "chips": {"/device:TPU:0": [(0.0, 0.8, "a"), (1.5, 2.0, "b"),
                                      (3.6, 4.0, "c"), (5.0, 6.0, "d")]}}
    a = spans.reduce(ev, KERNELS)
    r = trace.reduce(ev, KERNELS)
    assert sorted(a["idle_gaps"]) == sorted(r["idle_gaps"])
    assert sorted(name for name, _ in a["idle_gaps"]) == [
        "gateway gap", "node.step gap", "node.step gap"]
    assert a["idle_by_span"] == pytest.approx(
        {"bench:node.step": 0.5 + 1.0, "unattributed": 0.2 + 0.6 + 1.0})


def test_the_chip_with_most_idle_is_read():
    ev = nested_events()
    ev["chips"]["/device:TPU:1"] = [(0.0, 9.0, "fusion bf16[4]")]
    assert sum(spans.reduce(ev, KERNELS)["idle_by_span"].values()) \
        == pytest.approx(10.0 - 0.5 - 1.5 - 1.1 - 0.5)
    ev["chips"]["/device:TPU:1"] = [(0.0, 0.1, "fusion bf16[4]")]
    a = spans.reduce(ev, KERNELS)
    assert sum(a["idle_by_span"].values()) == pytest.approx(9.9)


def test_no_window_no_attribution():
    with pytest.raises(ValueError):
        spans.reduce({"spans": [], "program_spans": [], "chips": {}},
                     KERNELS)


# ------------------------------------------------------------------ readers

def window(trace_=None, stats0=None, stats1=None):
    return types.SimpleNamespace(
        trace=trace_, stats0=stats0 or [{}], stats1=stats1 or [{}],
        cell=types.SimpleNamespace(serving={"max_slots": 16,
                                            "prefill_chunk_tokens": 256}))


def test_readers_of_the_new_metrics():
    a = spans.reduce(nested_events(), KERNELS)
    a["span_durations"]["engine.horizon"] = [0.38, 0.5, 0.4]
    w = window(a,
               [{"engine_chunk_launches": 10, "engine_chunk_tokens": 1000}],
               [{"engine_chunk_launches": 20, "engine_chunk_tokens": 3048}])
    read = {m: R.read_metric(ROOT, m, w) for m in (
        "gateway_idle_share", "engine_idle_share", "horizon_launch_ms",
        "chunk_launch_ms", "chunk_fill_share")}
    assert read == pytest.approx({
        "gateway_idle_share": 100 * (1.5 + 0.4 + 0.5) / 10,
        "engine_idle_share": 100 * (1.1 + 0.6 + 0.5) / 10,
        "horizon_launch_ms": 400.0, "chunk_launch_ms": 2600.0,
        # 2048 prompt tokens in 10 launches of 16 x 256 positions
        "chunk_fill_share": 5.0})
    dev = R.read_metric(ROOT, "device_idle_share",
                        window(trace.reduce(nested_events(), KERNELS)))
    assert read["gateway_idle_share"] + read["engine_idle_share"] <= dev


def test_readers_find_nothing_in_a_program_without_spans_or_counters():
    w = window(trace.reduce(nested_events(), KERNELS),
               [{"engine_decode_tokens": 0}], [{"engine_decode_tokens": 8}])
    for m in ("gateway_idle_share", "engine_idle_share", "horizon_launch_ms",
              "chunk_launch_ms", "chunk_fill_share"):
        assert R.read_metric(ROOT, m, w) is None
    assert R.read_metric(ROOT, "chunk_launch_ms", window()) is None


# ------------------------------------------------------- a recorded slice

SLICE = Path(__file__).resolve().parent / "data" / "trace_v5e_spans.json"


def test_recorded_v5e_slice_names_the_publish_gap():
    """0.55 s of a traced window of qwen3-8b.l24.interactive-teams on a TPU
    v5e, around its longest idle stretch (``trace.extract``'s form with
    ``program_spans``; times from the slice's start): a chunk launch that
    ends seven prompts, whose publication into the prefix index holds the
    chip idle for 0.46 s; the harness alone names that gap ``node.step``."""
    events = json.loads(SLICE.read_text())
    kernels = trace.kernel_names(ROOT / "bench" / "kernels")
    r = trace.reduce(events, kernels)
    a = spans.reduce(events, kernels)
    assert r["idle_gaps"][0][0] == "node.step gap"
    assert a["idle_gaps"][0] == ["engine.publish gap",
                                 pytest.approx(r["idle_gaps"][0][1])]
    assert len(a["span_durations"]["engine.publish"]) == 7
    idle = a["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert max(idle, key=idle.get) == "engine.publish"
    bare = {k: v for k, v in events.items() if k != "program_spans"}
    assert trace.reduce(bare, kernels) == r


# ------------------------------------------------- the run, spans on

def test_cpu_rehearsal_with_spans_on(tiny_root):
    """The whole run on the CPU at test widths with the program's spans on,
    recorded by a factory: still correct, every layer's spans there, those
    of one request under one ``req``, the harness's recording in
    ``bench:record``; spans off and the harness as it was afterwards."""
    from repro.serving import telemetry
    seen = []

    class Recording:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **args):
            seen[-1][1].update(args)

    before = (H.serve_window, H.Recorder, trace.extract, trace.reduce,
              R.metric_names)
    with spans.spans_on(Recording):
        out = cpu_run(tiny_root)
    assert out["correct"], out["checks"]
    names = {name for name, _ in seen}
    assert {"maestro:" + n for n in (
        "gateway.step", "gateway.dispatch", "gateway.collect",
        "gateway.finish", "node.submit", "node.step", "engine.step",
        "engine.admit", "engine.chunk", "engine.chunk.sync",
        "engine.publish", "engine.horizon", "engine.horizon.sync",
        "engine.release")} <= names
    assert "bench:record" in names
    req = next(a["req"] for n, a in seen if n == "maestro:gateway.finish")
    assert {n for n, a in seen if a.get("req") == req} >= {
        "maestro:node.submit", "maestro:engine.prefix_lookup",
        "maestro:gateway.finish"}
    assert telemetry.span("engine.step") is telemetry.NULL_SPAN
    assert (H.serve_window, H.Recorder, trace.extract, trace.reduce,
            R.metric_names) == before
