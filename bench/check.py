"""The comparison that decides ``correct``: what the timed path served,
against the plain reference.

Once the window has closed, a sample of the stages it finished is drawn
from the seed: the one with the most tokens, at least one from every
replica, and others at random up to the configuration's sample size. For
each, the reference runs once over its prompt and the tokens it was served,
and at every position where a token was served it reads by how much the
served token's logit lies below the reference's best there. The widest such
gap over the sample is the number compared.

Why this number: the program decodes greedily in bfloat16, so wherever its
logits agree with the float32 reference to within the rounding of bfloat16
weights and KV, the token it serves is the reference's best or one whose
logit is within that rounding of the best. A wrong page, position, mask,
head mapping or layer moves the logits by far more than rounding, and the
served tokens then lie well below the reference's best.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from bench import reference
from bench.traffic import _seed_words


def sample(finished: Dict[int, tuple], node_of: Dict[int, int], seed: int,
           n: int) -> List[int]:
    """Stage ids to compare: the longest (prompt + served tokens), one from
    each replica, then random ones, ``n`` in all (fewer if fewer finished).
    ``finished`` maps stage id -> (prompt tokens, served tokens)."""
    ids = sorted(finished)
    if not ids:
        return []
    rng = np.random.default_rng(_seed_words(seed) + [7])
    size = {s: len(finished[s][0]) + len(finished[s][1]) for s in ids}
    out = [max(ids, key=lambda s: (size[s], s))]
    for node in sorted(set(node_of[s] for s in ids)):
        mine = [s for s in ids if node_of[s] == node and s not in out]
        if mine and not any(node_of[s] == node for s in out):
            out.append(mine[int(rng.integers(len(mine)))])
    rest = [s for s in ids if s not in out]
    rng.shuffle(rest)
    return out + rest[:max(0, n - len(out))]


def _batch(items: Sequence[tuple], t_len: int, k_len: int, n_rows: int):
    """tokens [N, T]: prompt + served tokens but the last, padded; rows
    [N, K]: the positions whose logits chose each served token; served
    [N, K] (-1 past the end). N is ``n_rows`` whatever the sample's size,
    so that one compiled reference serves every run of a cell."""
    N = max(n_rows, len(items))
    tokens = np.zeros((N, t_len), np.int32)
    rows = np.zeros((N, k_len), np.int32)
    served = np.full((N, k_len), -1, np.int32)
    for i, (prompt, out) in enumerate(items):
        seq = list(prompt) + list(out[:-1])
        if len(seq) > t_len or len(out) > k_len:
            raise ValueError(f"a {len(prompt)}+{len(out)}-token sequence "
                             f"does not fit the {t_len}x{k_len} check")
        tokens[i, :len(seq)] = seq
        rows[i, :len(out)] = np.arange(len(prompt) - 1,
                                       len(prompt) - 1 + len(out))
        served[i, :len(out)] = out
    return tokens, rows, served


def gaps(conf: dict, params, items: Sequence[tuple], t_len: int,
         k_len: int, control: bool = False) -> np.ndarray:
    """Per served position (flattened, padding dropped): the reference's
    best logit less the logit of the token served there; with ``control``,
    of the token the float8 control puts first at that position instead."""
    tokens, rows, served = _batch(items, t_len, k_len,
                                  int(conf["check"]["sample_requests"]))
    valid = served >= 0
    pick = served
    if control:
        _, arg, _ = reference.logits_at(conf, params, tokens, rows,
                                        np.full_like(served, -1), "fp8")
        pick = np.where(valid, np.asarray(arg), -1).astype(np.int32)
    best, _, at = reference.logits_at(conf, params, tokens, rows, pick)
    g = np.asarray(best, np.float64) - np.asarray(at, np.float64)
    return g[valid]


def widest_gap(g: np.ndarray) -> Optional[float]:
    return float(np.max(g)) if g.size else None


def judge(cell, params, items: Sequence[tuple], compiles: int,
          control: bool = False):
    """The run's verdict: (correct, checks, gaps). ``checks`` holds each
    number compared beside its limit: the widest logit gap of the served
    tokens (with ``control``, of the float8 control's tokens at the same
    positions) against the configuration's ``logit_gap_limit``, and the
    programs compiled in the window against 0. No sample, or a gap that is
    not finite, is not correct."""
    conf = cell.config
    g = np.zeros(0)
    if items:
        g = gaps(conf, params, items, int(conf["serving"]["s_max"]),
                 int(cell.mix["output_cap"]), control)
    widest = widest_gap(g)
    limit = float(conf["check"]["logit_gap_limit"])
    checks = {"logit_gap_max": {"value": widest, "limit": limit},
              "window_compiles": {"value": int(compiles), "limit": 0}}
    correct = (widest is not None and bool(np.isfinite(widest))
               and widest <= limit and compiles == 0)
    return correct, checks, g
