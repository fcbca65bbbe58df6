#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: for each seed, serve a
window at the cell's own load as a benchmark run does, then judge the
sample it draws twice with the run's own verdict: as served (the program's
reading), and with the tokens that the float8 control puts first at the
same positions (the control's reading, whose ``correct`` has to be false).
All seeds run in one process.

  python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

The benchmark's own runs never run the control. The limit in the
configuration file lies above the largest program reading and below the
smallest control reading (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float, devices, predictor,
             compiles, control: bool = True) -> dict:
    """One seed: the program's verdict and, with ``control``, the
    control's, over the same sample."""
    from bench import check, harness as H
    s = H.serve_once(cell, seed, seconds, devices, compiles,
                     time.perf_counter(), predictor=predictor)
    out = {"seed": seed, "stages": len(s.items)}
    runs = [("program", False)] + ([("control", True)] if control else [])
    for name, ctl in runs:
        correct, checks, g = check.judge(cell, s.weights, s.items,
                                         s.window.compiles, control=ctl)
        out[name] = {"correct": correct, "checks": checks,
                     "tokens": int(g.size), "off_best": int((g > 0).sum())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control on the first N seeds only")
    args = ap.parse_args(argv)
    import jax
    from bench import harness as H, run as R
    cell = H.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's chips on a TPU", file=sys.stderr)
        return 1
    R.enable_cache(jax, ROOT)
    predictor = H.train_predictor()
    compiles = H.compile_counter()
    seeds = [int(s) for s in args.seeds.split(",")]
    n_ctl = len(seeds) if args.control_seeds is None else args.control_seeds
    for i, seed in enumerate(seeds):
        print(json.dumps(readings(cell, seed, args.seconds, devices,
                                  predictor, compiles, control=i < n_ctl)),
              flush=True)
    return 0


if __name__ == "__main__":
    from bench.run import _pin_hash_seed
    _pin_hash_seed()
    sys.exit(main())
