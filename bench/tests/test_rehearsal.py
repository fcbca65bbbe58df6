"""The whole run on the CPU at test widths: everything but the look for a
chip. Also: no TPU, no result."""
import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path


from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SECONDS = 14.0


def cpu_run(root, seed=2**33 + 5, trace=0):
    args = argparse.Namespace(workload="tiny.teams", seed=seed,
                              seconds=SECONDS, trace=trace)
    return R.run(args, root=root, require_tpu=False, peaks=PEAKS,
                 t_start=time.perf_counter())


def test_cpu_rehearsal_end_to_end(tiny_root):
    out = cpu_run(tiny_root)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["window_compiles"]["value"] == 0
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    m = out["metrics"]
    assert m["tokens_per_s"]["value"] > 0
    assert m["itl_p95_ms"]["value"] > 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen3-8b.l24.interactive-teams", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "found no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    """A checkout with only BENCHMARK.json and bench/ (no program)."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-8b.l24.interactive-teams", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_fault_first_token_altered(tiny_root, monkeypatch):
    """A token altered where the prefill produces it: correct is false."""
    from repro.serving.engine import Engine
    first = Engine._first_token

    def altered(self, req, tok):
        first(self, req, (tok + 1) % self.model.cfg.vocab)

    monkeypatch.setattr(Engine, "_first_token", altered)
    out = cpu_run(tiny_root)
    assert not out["correct"]
    assert out["checks"]["logit_gap_max"]["value"] > \
        out["checks"]["logit_gap_max"]["limit"]


def test_fault_horizon_token_altered(tiny_root, monkeypatch):
    """A token altered where the on-device decode horizon produces it."""
    import jax.numpy as jnp
    from repro.serving.engine import Engine
    init = Engine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        fwd = self._horizon_fwd

        def wrong(*args):
            blk, *rest = fwd(*args)
            blk = jnp.where(blk > 0, blk - 1, blk)
            return (blk, *rest)

        self._horizon_fwd = wrong

    monkeypatch.setattr(Engine, "__init__", patched)
    out = cpu_run(tiny_root)
    assert not out["correct"]
