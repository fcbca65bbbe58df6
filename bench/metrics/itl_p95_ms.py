"""Gaps between consecutive output tokens of a stage as the host receives
them, at the 95th percentile over every gap in the window. The tokens of one
horizon launch arrive together: all but the first of them arrive with a gap
of 0, as a streaming user would see them."""
from bench.harness import percentile


def read(w):
    gaps = [g for g, t in zip(w.rec.gaps, w.rec.gap_t) if t <= w.seconds]
    v = percentile(gaps, 95)
    return None if v is None else 1e3 * v
