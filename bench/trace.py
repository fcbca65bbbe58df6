"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time, by code kept with the benchmark so that every change is read alike.

The harness wraps the measured window in a ``bench:window`` annotation and
every node step in ``bench:node.step``; the profiler puts these host spans
and the chips' operations on one clock. For each chip (a ``/device:TPU:<n>``
plane) this reads the ``XLA Ops`` line:

- busy: the union of the intervals in which an operation ran, inside the
  window; idle is the window less busy;
- kernel time: the summed durations of the operations whose HLO
  instruction is named after a kernel (the program's Pallas calls take the
  names of its jitted kernel functions: ``paged_attention``,
  ``chunk_prefill_attention``);
- idle gaps: each stretch with no operation, named after the innermost
  harness span the host was in over most of it (``node.step``: the engine's
  own host work between launches; ``window``: the gateway loop outside any
  node step).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
MAX_ENTRIES = 10


def latest_xplane(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


CONTAINERS = ("while", "conditional", "call")
_INST = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.remat\d*)?"
                   r"(?:\.\d+)* = (\S+)")


def op_label(name: str) -> str:
    """A stable name for a device operation from its HLO text (``%fusion.143
    = bf16[16,256,12288]{...} fusion(...)``): the instruction's base name,
    and its result shape where that is one array: ``fusion
    bf16[16,256,12288]``."""
    m = _INST.match(name)
    if not m:
        return name[:80]
    base, shape = m.group(1), m.group(2)
    if shape.startswith("("):
        return base
    return f"{base} {shape.split('{')[0]}"


def op_base(label: str) -> str:
    return label.split(" ")[0]


def extract(profile) -> dict:
    """The parts of a profiler trace the reduction reads, as plain lists:
    ``spans`` [(name, start s, end s)] of the harness's ``bench:`` host
    annotations, and ``chips`` {plane: [(start s, end s, label)]} of every
    chip's ``XLA Ops`` line (see :func:`op_label`)."""
    spans, chips = [], {}
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        spans.append((ev.name[len("bench:"):],
                                      ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = []
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    ops.append((t0, t0 + ev.duration_ns * 1e-9,
                                op_label(ev.name)))
                chips[plane.name] = ops
    return {"spans": spans, "chips": chips}


def reduce(events: dict, kernels: Dict[str, Sequence[str]]) -> dict:
    """Busy time, idle gaps and kernel time of every chip over the
    ``bench:window`` span. ``events`` is what :func:`extract` returns;
    ``kernels`` maps a kernel to the instruction names of its operations
    (an op is the kernel's where its base name is one of them). Loops and
    calls (``while``...) hold other ops: they count towards busy time but
    not in the op breakdown."""
    spans = events["spans"]
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise ValueError("the trace holds no bench:window span")
    w0, w1 = win[0][1], win[0][2]
    steps = [(a, b) for name, a, b in spans if name == "node.step"]
    chips = events["chips"]
    per_chip = []
    op_time: Dict[str, float] = collections.Counter()
    kernel_s: Dict[str, float] = {k: 0.0 for k in kernels}
    kernel_n: Dict[str, int] = {k: 0 for k in kernels}
    gaps: List[Tuple[str, float]] = []
    for name in sorted(chips):
        ops = [(max(a, w0), min(b, w1), lab)
               for a, b, lab in chips[name] if b > w0 and a < w1]
        busy = union([(a, b) for a, b, _ in ops])
        busy_s = sum(b - a for a, b in busy)
        for a, b, lab in ops:
            base = op_base(lab)
            if base in CONTAINERS:
                continue
            op_time[lab] += b - a
            for k, names in kernels.items():
                if base in names:
                    kernel_s[k] += b - a
                    kernel_n[k] += 1
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            in_step = sum(_overlap((a, b), s) for s in steps)
            gaps.append(("node.step" if in_step > (b - a) / 2 else "gateway",
                         b - a))
        per_chip.append({"chip": name, "busy_s": busy_s,
                         "idle_share": 1.0 - busy_s / (w1 - w0),
                         "n_ops": len(ops)})
    if not per_chip:
        raise ValueError("the trace holds no device operations")
    idle_by = collections.Counter()
    for what, d in gaps:
        idle_by[what] += d
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:MAX_ENTRIES]
    return {
        "window_s": w1 - w0,
        "chips": per_chip,
        "busy_s": sum(c["busy_s"] for c in per_chip) / len(per_chip),
        "kernel_s": kernel_s,
        "kernel_calls": kernel_n,
        "device_ops": [[k, v] for k, v in op_time.most_common(MAX_ENTRIES)],
        "idle_gaps": [[f"{what} gap", d] for what, d in top_gaps],
        "idle_by_host_span": dict(idle_by),
    }


def kernel_names(kernels_dir) -> Dict[str, Sequence[str]]:
    """{kernel: names of its operations} from every module of
    ``bench/kernels`` that declares ``NAMES``."""
    import importlib.util
    out = {}
    for path in sorted(glob.glob(os.path.join(str(kernels_dir), "*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        if stem.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            f"bench_kernel_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if hasattr(mod, "NAMES"):
            out[stem] = tuple(mod.NAMES)
    return out
