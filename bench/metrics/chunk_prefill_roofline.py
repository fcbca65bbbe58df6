"""Kernels: the least time the chunked-prefill attention kernel needs for
the prompt chunks the window ran (each chunk at the larger of its FLOPs
over the bf16 peak and its K/V bytes over HBM bandwidth; compute bound at
these chunk widths) over its time in the device trace, in percent."""
from bench import work


def read(w):
    if not w.trace or not w.trace["kernel_calls"].get("chunk_prefill"):
        return None
    need, _, _ = work.chunk_prefill_need(w, w.peaks)
    return 100.0 * need / w.trace["kernel_s"]["chunk_prefill"]
