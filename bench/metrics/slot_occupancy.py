"""Engine: running requests over decode slots, averaged over the samples
taken after every node step of the window, in percent: how full the
continuous batch is kept (admission and KV reservation decide it)."""


def read(w):
    running = sum(r for _, r, _ in w.rec.active)
    slots = sum(s for _, _, s in w.rec.active)
    return 100.0 * running / slots if slots else None
