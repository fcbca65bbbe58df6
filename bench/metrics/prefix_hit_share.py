"""KV arena and prefix cache: prompt tokens served from cached pages over
the prompt tokens of every request the engines admitted in the window, in
percent (the engine's own ``prefill_avoided`` count)."""


def read(w):
    total = hit = 0
    for req in w.rec.reqs.values():
        total += len(req.tokens)
        hit += int(req.prefill_avoided)
    return 100.0 * hit / total if total else None
