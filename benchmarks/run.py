"""Benchmark driver — one benchmark per paper table/figure.

  python -m benchmarks.run              # full pass (tens of minutes)
  python -m benchmarks.run --fast       # reduced sizes (CI / smoke)
  python -m benchmarks.run --smoke      # tiny sizes, subset policies (CI)
  python -m benchmarks.run --only table5_memory fig10_activation
  python -m benchmarks.run --smoke --only gateway --backend process
                                        # live gateway on worker processes
  python -m benchmarks.run --smoke --only gateway --backend socket
                                        # live gateway over the framed-TCP
                                        # socket transport (localhost)
  python -m benchmarks.run --smoke --only gateway --clock wall
                                        # wall-clock gateway (real elapsed
                                        # time, inproc vs process fleets)
  python -m benchmarks.run --smoke --only gateway_socket
                                        # socket parity + wall overhead +
                                        # kill-a-worker fault injection
"""
from __future__ import annotations

import argparse
import hashlib
import json
import platform
import subprocess
import time
import traceback

BENCHES = {}

# CI smoke runs one sim policy and one live-gateway policy end-to-end so the
# benchmark entry points can't silently rot
SMOKE_POLICIES = ("fcfs", "maestro")


def _register(mode: str, backend: str = "inproc",
              clock: str = "virtual") -> None:
    from benchmarks import (activation, colocation, decode_horizon,
                            engine_batching, fitness, gateway, kernels,
                            memory, prediction, preemption, prefix_reuse,
                            scheduling, tail_scenarios)
    fast = mode != "full"
    smoke = mode == "smoke"
    if clock == "wall":
        # wall rows are machine-dependent: smoke asserts completion only
        # (max_run_s-capped so a hung fleet fails fast instead of wedging
        # CI); sized runs additionally assert the process-fleet speedup
        gateway_bench = lambda: gateway.wall_main(  # noqa: E731
            n_jobs={"full": 96, "fast": 64, "smoke": 4}[mode],
            rate={"full": 16.0, "fast": 16.0, "smoke": 2.0}[mode],
            max_run_s={"full": 1800.0, "fast": 900.0, "smoke": 300.0}[mode],
            gen_cap={"full": 48, "fast": 48, "smoke": 8}[mode],
            repeats=1 if smoke else 2,
            assert_speedup=not smoke)
    else:
        gateway_bench = lambda: gateway.main(  # noqa: E731
            n_jobs={"full": 240, "fast": 24, "smoke": 5}[mode], fast=fast,
            policies=SMOKE_POLICIES if smoke else None, backend=backend)
    BENCHES.update({
        "gateway": gateway_bench,
        "gateway_socket": lambda: gateway.socket_main(
            n_jobs={"full": 48, "fast": 12, "smoke": 5}[mode],
            fault_jobs=6),
        "decode_horizon": lambda: decode_horizon.main(
            n_jobs={"full": 24, "fast": 12, "smoke": 4}[mode],
            gen_cap={"full": 16, "fast": 12, "smoke": 6}[mode],
            max_new={"full": 96, "fast": 48, "smoke": 12}[mode],
            max_run_s={"full": 1800.0, "fast": 900.0, "smoke": 300.0}[mode],
            repeats=1 if smoke else 2,
            backend=backend,
            assert_speedup=not smoke),
        "engine_batching": lambda: engine_batching.main(
            n_jobs={"full": 32, "fast": 24, "smoke": 4}[mode],
            rate={"full": 8.0, "fast": 8.0, "smoke": 2.0}[mode],
            gen_cap={"full": 24, "fast": 16, "smoke": 6}[mode],
            max_run_s={"full": 1800.0, "fast": 900.0, "smoke": 300.0}[mode],
            repeats=1 if smoke else 2,
            backend=backend,
            assert_speedup=not smoke),
        "tail_scenarios": lambda: tail_scenarios.main(
            n_jobs={"full": 1000, "fast": 150, "smoke": 30}[mode],
            fault_jobs={"full": 48, "fast": 24, "smoke": 10}[mode],
            policies=SMOKE_POLICIES if smoke else None,
            clock=clock,
            max_run_s={"full": 1800.0, "fast": 900.0, "smoke": 300.0}[mode]),
        "prefix_reuse": lambda: prefix_reuse.main(
            n_jobs={"full": 96, "fast": 24, "smoke": 10}[mode], fast=fast,
            backend=backend, include_wall=(mode == "full")),
        "table3_6_7_prediction": lambda: prediction.main(
            n_jobs=800 if fast else 2500),
        "fig7_scheduling": lambda: scheduling.main(
            n_jobs={"full": 600, "fast": 250, "smoke": 250}[mode], fast=fast,
            policies=SMOKE_POLICIES if smoke else None),
        "table2_preemption": lambda: preemption.main(
            n_jobs=200 if fast else 400, fast=fast),
        "table4_colocation": lambda: colocation.main(fast=fast),
        "table5_memory": lambda: memory.main(fast=fast),
        "table8_fitness": lambda: fitness.main(
            n_jobs=250 if fast else 500, fast=fast),
        "fig10_activation": lambda: activation.main(fast=fast),
        "kernels": lambda: kernels.main(fast=fast),
    })


# headline metric per BENCH file (all higher-is-better): a re-run that lands
# >20% below the persisted value prints a loud regression warning BEFORE the
# file is overwritten — the trajectory record stays honest without making
# machine-dependent wall numbers a hard CI gate
HEADLINES = {
    "decode_horizon": "decode_speedup_h8_x",
    "engine_batching": "chunked_speedup_x",
    "prefix_reuse": "prefill_avoided_frac",
}
REGRESSION_FRAC = 0.20


def check_headline_regression(name: str, payload: dict) -> None:
    """Compare a bench payload's headline metric against the persisted
    BENCH_<name>.json (if any) and warn on a >20% drop. Comparison is
    best-effort: missing files, keys or zero baselines are silent."""
    base = name
    for sfx in ("_backend", "_wall", "_process", "_socket"):
        if base.endswith(sfx):
            base = base[:-len(sfx)]
    key = HEADLINES.get(name) or HEADLINES.get(base)
    if key is None or not isinstance(payload, dict):
        return
    from benchmarks.common import RESULTS
    prev_file = RESULTS / f"BENCH_{name}.json"
    if not prev_file.exists():
        return
    try:
        prev = json.loads(prev_file.read_text()).get(key)
    except (json.JSONDecodeError, OSError):
        return
    cur = payload.get(key)
    if not isinstance(prev, (int, float)) or prev <= 0 \
            or not isinstance(cur, (int, float)):
        return
    drop = (prev - cur) / prev
    if drop > REGRESSION_FRAC:
        print(f"[run] WARNING: {name} headline {key} regressed "
              f"{drop:.0%} ({prev} -> {cur}); persisted baseline will be "
              f"overwritten — investigate before trusting the new row")


def repro_stamp(payload: dict) -> dict:
    """Reproducibility stamp for persisted BENCH payloads: the exact source
    revision, the host that produced the row, and a fingerprint of the
    payload's own config scalars (everything but the result rows) — so two
    BENCH files are comparable iff their stamps match."""
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             check=True).stdout.strip()
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip())
    except Exception:
        sha, dirty = "unknown", False
    cfg = {k: v for k, v in payload.items()
           if not isinstance(v, (list, dict)) or k in ("policies", "zoo")}
    fp = hashlib.sha256(
        json.dumps(cfg, sort_keys=True, default=str).encode()).hexdigest()
    return {"git_sha": sha, "git_dirty": dirty, "host": platform.node(),
            "config_fingerprint": fp[:16]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + policy subset (CI entry-point check)")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--backend", choices=("inproc", "process", "socket"),
                    default="inproc",
                    help="gateway node backend: cooperative in-process "
                         "runtimes (default), one worker process per node "
                         "(pipes), or worker processes over the framed-TCP "
                         "socket transport")
    ap.add_argument("--clock", choices=("virtual", "wall"),
                    default="virtual",
                    help="gateway clock: deterministic virtual ticks "
                         "(default) or real wall time (runs BOTH node "
                         "backends and reports the process-fleet speedup; "
                         "rows land in BENCH_gateway_wall.json)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    mode = "smoke" if args.smoke else "fast" if args.fast else "full"
    _register(mode, backend=args.backend, clock=args.clock)
    names = args.only or list(BENCHES)
    failures = []
    t_all = time.time()
    for name in names:
        t0 = time.time()
        try:
            payload = BENCHES[name]()
            if payload is not None:
                # machine-readable perf record (e.g. BENCH_gateway.json) so
                # the trajectory is trackable across PRs; non-default node
                # backends and the wall clock get their own files
                # (BENCH_gateway_process.json / BENCH_gateway_wall.json) so
                # they never clobber the virtual in-process baseline record
                from benchmarks.common import save_result
                suffix = ""
                if isinstance(payload, dict):
                    if payload.get("clock", "virtual") == "wall":
                        suffix = "_wall"
                    elif payload.get("node_backend", "inproc") != "inproc":
                        suffix = f"_{payload['node_backend']}"
                        if f"{name}{suffix}" in BENCHES:
                            # a dedicated bench owns that filename (e.g.
                            # gateway_socket): disambiguate the generic
                            # backend-swept rows
                            suffix += "_backend"
                    payload["repro"] = repro_stamp(payload)
                    check_headline_regression(f"{name}{suffix}", payload)
                try:
                    save_result(f"BENCH_{name}{suffix}", payload)
                except TypeError as e:   # non-JSON payload: keep bench green
                    print(f"[run] {name}: payload not serializable ({e})")
            print(f"[run] {name} OK ({time.time()-t0:.0f}s)")
        except Exception as e:
            failures.append((name, e))
            traceback.print_exc()
            print(f"[run] {name} FAILED: {e}")
    print(f"\n[run] {len(names)-len(failures)}/{len(names)} benchmarks OK "
          f"({time.time()-t_all:.0f}s total)")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
