#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in BENCHMARK.json) names a configuration file and a
traffic mix; the harness builds one node per chip, draws the weights there
from the seed, warms every program the traffic uses (set-up, reported as
``setup_s``), then serves the seed's backlog of jobs for ``--seconds`` on
the wall clock through the program's gateway. After the window it checks
what was served against the plain float32 reference (``correct``), and
prints one JSON line: with ``--trace 0`` the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the harness's own records.

It needs a TPU with as many chips as the cell asks for: where JAX finds
none, or too few, it exits non-zero and prints no result. JAX's compilation
cache lives in ``<checkout>/.jax_cache``, so only a checkout's first run of
a cell compiles.
"""
from __future__ import annotations

import os
import sys
import time

T_START = time.perf_counter()


def _pin_hash_seed() -> None:
    """The program's length predictor hashes words with Python's ``hash``,
    which differs from process to process; a fixed hash seed makes every run
    of a seed offer the scheduler the same predictions."""
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)


if __name__ == "__main__":
    _pin_hash_seed()

import argparse
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(jax, root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, however quick its compile."""
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def metric_names(spec: dict, cell: str, trace: bool):
    """The cell's metrics for this kind of run, in BENCHMARK.json order."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(root: Path, name: str, window):
    import importlib.util
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(window)


def run(args, root: Path = ROOT, require_tpu: bool = True,
        peaks: dict = None, t_start: float = None) -> dict:
    """One run; returns the result line as a dict, or raises SystemExit
    with a non-zero code where the run cannot be made."""
    from bench import check, harness as H
    t_start = T_START if t_start is None else t_start
    cell = H.load_cell(args.workload, root)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        log(f"found no TPU (JAX platform {dev.platform!r}); the benchmark "
            f"runs only on the chip")
        raise SystemExit(1)
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        raise SystemExit(1)
    if peaks is None:
        table = H.load_json(root / "bench" / "peaks.json")
        if dev.device_kind not in table:
            log(f"no peaks for device kind {dev.device_kind!r} in "
                f"bench/peaks.json")
            raise SystemExit(2)
        peaks = table[dev.device_kind]
    if dev.platform != "cpu":
        log(f"compile cache {enable_cache(jax, root)}")

    served = H.serve_once(
        cell, args.seed, args.seconds, devices, H.compile_counter(),
        t_start, trace_dir=root / ".bench_trace" if args.trace else None,
        root=root)
    w = served.window
    w.peaks = peaks
    rec = w.rec
    log(f"set-up {w.setup_s:.3f} s; window: {len(rec.first_t)} stages got "
        f"tokens, {len(rec.finish_t)} finished, {w.compiles} compiles")

    # ---- correctness, after the window, with the program's state freed
    t0 = time.perf_counter()
    correct, checks, g = check.judge(cell, served.weights, served.items,
                                     w.compiles)
    log(f"reference over {len(served.items)} stages, {g.size} served "
        f"tokens, {time.perf_counter() - t0:.1f} s")

    # ---- metrics
    metrics = {}
    for m in metric_names(cell.spec, cell.name, bool(args.trace)):
        v = read_metric(root, m["name"], w)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    rows = w.stage_table()
    failed = sum(1 for s in rows if s in rec.truncated)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": served.memory_peak_bytes}
    out = {"correct": bool(correct), "attempted": len(rows),
           "failed": failed, "metrics": metrics, "device": device}
    if w.trace:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
        out["breakdown"] = {"device_ops": w.trace["device_ops"],
                            "idle_gaps": w.trace["idle_gaps"]}
        log(f"idle by host span: {w.trace['idle_by_host_span']}; kernels "
            f"{w.trace['kernel_s']} s in {w.trace['kernel_calls']} calls")
    for name, m in metrics.items():
        log(f"{name} = {m['value']} {m['unit']}")
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args)
    except SystemExit as e:
        return int(e.code or 1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
