"""Shared set-up for the benchmark's own tests (run on the CPU:
``python -m pytest bench/tests``)."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = Path(__file__).resolve().parent / "data"


def make_root(tmp: Path, jobs: int = 12) -> Path:
    """A checkout-shaped directory holding one test cell: the tiny
    configuration under the interactive-teams traffic, a backlog of ``jobs``
    served by one CPU device; the metric readers are the real ones."""
    bench = tmp / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    os.symlink(ROOT / "bench" / "metrics", bench / "metrics")
    shutil.copy(DATA / "tiny.json", bench / "configs" / "tiny.json")
    mix = json.loads((ROOT / "bench" / "traffic" /
                      "interactive-teams.json").read_text())
    mix["arrival"] = {"kind": "backlog", "jobs": jobs}
    (bench / "traffic" / "tiny-teams.json").write_text(json.dumps(mix))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tiny.teams", "config": "tiny",
                          "traffic": "tiny-teams", "chips": 1,
                          "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = ["tiny.teams"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
