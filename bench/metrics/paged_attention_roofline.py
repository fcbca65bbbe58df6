"""Kernels: the least time the paged decode attention kernel needs for the
window's decode tokens (memory bound: K and V bytes over the chip's HBM
bandwidth) over its time in the device trace, in percent."""
from bench import work


def read(w):
    if not w.trace or not w.trace["kernel_calls"].get("paged_attention"):
        return None
    need, _, _ = work.paged_attention_need(w, w.peaks)
    return 100.0 * need / w.trace["kernel_s"]["paged_attention"]
