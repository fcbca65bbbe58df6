#!/usr/bin/env python3
"""A traced run of ``bench/run.py`` with the program's spans on, and the
chips' idle time by those spans.

The program marks its layer boundaries with spans
(``repro.serving.telemetry.span``: ``gateway.*``, ``node.*``, ``engine.*``),
off unless a traced run turns them on; the profiler then records them as
``maestro:<name>`` on the host timeline, on the chips' clock. :func:`reduce`
cuts every idle stretch of a chip at span edges and gives each piece to the
innermost span covering it, the harness's ``bench:`` spans included, so
that the harness's own recording is not billed to the program.

  python3 bench/spans.py --workload <name> --seed <n> --seconds <s> \\
      [--events PATH]

makes one ``bench/run.py --trace 1`` run, judged as that run judges, with
the program's spans on and the harness's recording after each node step in
a ``bench:record`` span (:func:`spans_on`). It prints that run's result
line, its ``metrics`` joined by those read from the spans
(``SPAN_METRICS``) and its idle gaps named by span, and adds the idle
seconds by innermost span (``idle_by_span``), each span's count, median and
total duration (``spans``) and the number of program spans in the trace.
``--events`` writes what the reduction read (``trace.extract``'s form plus
``program_spans``) as JSON.
"""
from __future__ import annotations

import collections
import contextlib
import math
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as R  # noqa: E402  (puts src on the path)

if __name__ == "__main__":
    R._pin_hash_seed()

from bench import harness as H, trace as trace_red  # noqa: E402

PREFIX = "maestro:"
# read from ``idle_by_span`` and ``span_durations``, which only a run with
# the program's spans on holds: {metric: unit}
SPAN_METRICS = {"gateway_idle_share": "%", "engine_idle_share": "%",
                "horizon_launch_ms": "ms", "chunk_launch_ms": "ms"}
_trace_reduce = trace_red.reduce


def program_spans(profile) -> List[Tuple[str, float, float]]:
    """[(name, start s, end s)] of the program's ``maestro:`` host spans,
    names without the prefix."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name[len(PREFIX):], ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def _host_spans(events: dict) -> List[Tuple[str, float, float]]:
    """Every host span but the window: the harness's as ``bench:<name>``,
    the program's by their own names."""
    return [("bench:" + n, a, b) for n, a, b in events["spans"]
            if n != "window"] + list(events.get("program_spans", ()))


def _split_by_innermost(idle: Sequence[Tuple[float, float]], host):
    """For each idle interval, its seconds by the innermost host span
    covering each piece of it (the covering span that started last; of two
    that started together, the shorter), ``unattributed`` where none
    does."""
    pts = sorted([(a, 1, i) for i, (a, _, _) in enumerate(host)]
                 + [(b, 0, i) for i, (_, b, _) in enumerate(host)])
    per_gap = [collections.Counter() for _ in idle]
    active: set = set()
    first = 0

    def credit(t0: float, t1: float) -> None:
        nonlocal first
        if t1 <= t0:
            return
        if active:
            i = max(active, key=lambda k: (host[k][0], -host[k][1]))
            label = host[i][2]
        else:
            label = "unattributed"
        while first < len(idle) and idle[first][1] <= t0:
            first += 1
        k = first
        while k < len(idle) and idle[k][0] < t1:
            ov = min(t1, idle[k][1]) - max(t0, idle[k][0])
            if ov > 0:
                per_gap[k][label] += ov
            k += 1

    prev = -math.inf
    for t, starts, i in pts:
        credit(prev, t)
        if starts:
            active.add(i)
        else:
            active.discard(i)
        prev = t
    credit(prev, math.inf)
    return per_gap


def _gap_name(label: str) -> str:
    """A gap's name from the span holding most of it; the harness's names
    where no program span does (``node.step``: inside the harness's node
    step span; ``gateway``: outside every span)."""
    return {"unattributed": "gateway",
            "bench:node.step": "node.step"}.get(label, label)


def reduce(events: dict, kernels: Dict[str, Sequence[str]]) -> dict:
    """``trace.reduce``'s entries for ``events`` (its form, with
    ``program_spans`` added), all read as it reads them but ``idle_gaps``,
    which are named here after the span holding most of each gap; and, on
    the chip with the most idle time, the one ``device_idle_share``
    reports:

    - ``idle_by_span``: {innermost span: idle seconds}, ``unattributed``
      for idle time outside every span but the window;
    - ``span_durations``: {span: [durations of those wholly inside the
      window]}."""
    r = _trace_reduce(events, kernels)
    w0 = next(a for n, a, _ in events["spans"] if n == "window")
    w1 = w0 + r["window_s"]
    spans = _host_spans(events)
    durations: Dict[str, List[float]] = collections.defaultdict(list)
    for n, a, b in spans:
        if a >= w0 and b <= w1:
            durations[n].append(b - a)
    host = [(max(a, w0), min(b, w1), n) for n, a, b in spans
            if b > w0 and a < w1]
    chip = max(r["chips"], key=lambda c: c["idle_share"])["chip"]
    busy = trace_red.union([(max(a, w0), min(b, w1))
                            for a, b, _ in events["chips"][chip]
                            if b > w0 and a < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    per_gap = _split_by_innermost(idle, host)
    idle_by = collections.Counter()
    for split in per_gap:
        idle_by.update(split)
    gaps = sorted(((_gap_name(split.most_common(1)[0][0]), b - a)
                   for (a, b), split in zip(idle, per_gap) if split),
                  key=lambda g: -g[1])[:trace_red.MAX_ENTRIES]
    r.update(idle_by_span=dict(idle_by), span_durations=dict(durations),
             idle_gaps=[[f"{name} gap", d] for name, d in gaps])
    return r


@contextlib.contextmanager
def spans_on(annotate):
    """While open, runs of ``bench/run.py`` in this process serve their
    window with the program's spans on through ``annotate`` (a factory such
    as ``jax.profiler.TraceAnnotation``), with the harness's recording
    after each node step in a ``bench:record`` span; traced runs keep the
    program's spans, reduce with :func:`reduce` and read ``SPAN_METRICS``
    besides the cell's. Yields a dict that holds, after a traced run, the
    ``events`` it reduced and the reduction (``reduced``)."""
    from repro.serving import telemetry
    seen: dict = {}
    serve_window, recorder = H.serve_window, H.Recorder
    extract, metric_names = trace_red.extract, R.metric_names

    def spanned_window(*args, **kw):
        telemetry.enable_spans(annotate)
        try:
            return serve_window(*args, **kw)
        finally:
            telemetry.enable_spans(None)

    class SpannedRecorder(recorder):
        def stepped(self, node, t1, out):
            with annotate("bench:record"):
                super().stepped(node, t1, out)

    def spanned_extract(profile):
        events = extract(profile)
        events["program_spans"] = program_spans(profile)
        return events

    def spanned_reduce(events, kernels):
        seen["events"] = events
        seen["reduced"] = reduce(events, kernels)
        return seen["reduced"]

    def names(spec, cell, trace):
        return metric_names(spec, cell, trace) + (
            [{"name": n, "unit": u} for n, u in SPAN_METRICS.items()]
            if trace else [])

    patches = [(H, "serve_window", spanned_window),
               (H, "Recorder", SpannedRecorder),
               (trace_red, "extract", spanned_extract),
               (trace_red, "reduce", spanned_reduce),
               (R, "metric_names", names)]
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    for mod, name, f in patches:
        setattr(mod, name, f)
    try:
        yield seen
    finally:
        for mod, name, f in saved:
            setattr(mod, name, f)


def main(argv=None) -> int:
    import argparse
    import json

    import jax
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events", default=None)
    args = ap.parse_args(argv)
    args.trace = 1
    with spans_on(jax.profiler.TraceAnnotation) as seen:
        try:
            out = R.run(args)
        except SystemExit as e:
            return int(e.code or 1)
    r, events = seen["reduced"], seen["events"]
    out["program_spans"] = len(events["program_spans"])
    out["idle_by_span"] = dict(sorted(r["idle_by_span"].items(),
                                      key=lambda kv: -kv[1]))
    out["spans"] = {
        name: {"n": len(d), "median_ms": 1e3 * statistics.median(d),
               "total_s": sum(d)}
        for name, d in sorted(r["span_durations"].items())}
    R.log(f"idle by span: {out['idle_by_span']}")
    if args.events:
        Path(args.events).write_text(json.dumps(events))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
