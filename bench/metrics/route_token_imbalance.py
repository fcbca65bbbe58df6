"""Gateway: how evenly the router spread the decode work over the replicas,
from the engines' own counters over the window: the busiest node's decode
tokens (``engine_decode_tokens``) over the mean of all nodes'. 1 is an even
spread; a fleet of n nodes reads at most n."""


def read(w):
    tokens = [b["engine_decode_tokens"] - a["engine_decode_tokens"]
              for a, b in zip(w.stats0, w.stats1)]
    mean = sum(tokens) / len(tokens) if tokens else 0
    return max(tokens) / mean if mean else None
