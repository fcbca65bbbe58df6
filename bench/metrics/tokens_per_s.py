"""Output tokens that reached the host in the window, over the window."""


def read(w):
    n = sum(k for t, _, k in w.rec.tokens_t if t <= w.seconds)
    return n / w.seconds if n else None
