"""Model programs: model FLOPs of every prompt and decode token the window
processed (``bench/kernels/model_step.py``), over chips x the chip's bf16
peak x the window, in percent."""
from bench import work


def read(w):
    peak = w.peaks["bf16_flops_per_s"]
    f = work.model_flops(w)
    return 100.0 * f / (w.n_nodes * peak * w.seconds) if f else None
