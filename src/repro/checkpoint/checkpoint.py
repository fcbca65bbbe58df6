"""Fault-tolerant checkpointing: atomic save (tmp + rename), optional async
host-side write, and ELASTIC restore — a checkpoint written under one mesh
can be restored onto a different mesh (re-sharding happens at device_put
against the target NamedShardings), which is what elastic scaling needs.

Format: <dir>/step_<n>/ with arrays.npz (flat leaves) + manifest.json
(treedef + shapes + dtypes + step metadata).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


def _flatten(tree) -> Tuple[Dict[str, np.ndarray], Any]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}, treedef


def save(path: str, tree, step: int, extra: Optional[Dict] = None,
         async_: bool = False) -> Optional[threading.Thread]:
    """Atomic checkpoint: write to <path>/.tmp_step_<n>, fsync, rename."""
    base = Path(path)
    base.mkdir(parents=True, exist_ok=True)
    final = base / f"step_{step:08d}"
    tmp = base / f".tmp_step_{step:08d}"
    arrays, treedef = _flatten(tree)
    manifest = {
        "step": step,
        "treedef": treedef.serialize_using_proto().hex(),
        "n_leaves": len(arrays),
        "shapes": {k: list(v.shape) for k, v in arrays.items()},
        "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
        "extra": extra or {},
    }

    def _write():
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        np.savez(tmp / "arrays.npz", **arrays)
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        if final.exists():
            shutil.rmtree(final)
        os.replace(tmp, final)   # atomic publish

    if async_:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        return t
    _write()
    return None


def latest_step(path: str) -> Optional[int]:
    base = Path(path)
    if not base.exists():
        return None
    steps = sorted(int(p.name.split("_")[1]) for p in base.glob("step_*"))
    return steps[-1] if steps else None


def restore(path: str, like_tree, step: Optional[int] = None,
            shardings=None) -> Tuple[Any, Dict]:
    """Restore into the structure of ``like_tree``. ``shardings`` (a matching
    tree of NamedSharding / None) re-shards for the CURRENT mesh — restoring
    a 256-chip checkpoint onto 512 chips (or 1 CPU) just works."""
    step = step if step is not None else latest_step(path)
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {path}")
    d = Path(path) / f"step_{step:08d}"
    manifest = json.loads((d / "manifest.json").read_text())
    with np.load(d / "arrays.npz") as z:
        arrays = [z[f"leaf_{i}"] for i in range(manifest["n_leaves"])]
    leaves, treedef = jax.tree_util.tree_flatten(like_tree)
    assert len(leaves) == len(arrays), (len(leaves), len(arrays))
    if shardings is not None:
        shard_leaves = treedef.flatten_up_to(shardings)
        out = [jax.device_put(a.astype(l.dtype), s)
               for a, l, s in zip(arrays, leaves, shard_leaves)]
    else:
        out = [jax.device_put(a.astype(l.dtype)) for a, l in
               zip(arrays, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out), manifest["extra"]


def prune(path: str, keep: int = 3) -> None:
    base = Path(path)
    steps = sorted(base.glob("step_*"))
    for p in steps[:-keep]:
        shutil.rmtree(p)
