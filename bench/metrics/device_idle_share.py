"""Device: 1 - (union of the intervals in which an operation ran on a chip
over the traced window), per chip, the worst chip reported, in percent."""


def read(w):
    if not w.trace:
        return None
    return 100.0 * max(c["idle_share"] for c in w.trace["chips"])
