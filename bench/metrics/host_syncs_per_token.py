"""Engine: blocking device-to-host pulls on the decode path per decode
token, from the engine's own counters over the window (1/8 at best with an
8-token horizon)."""


def read(w):
    syncs = sum(b["engine_decode_syncs"] - a["engine_decode_syncs"]
                for a, b in zip(w.stats0, w.stats1))
    toks = sum(b["engine_decode_tokens"] - a["engine_decode_tokens"]
               for a, b in zip(w.stats0, w.stats1))
    return syncs / toks if toks else None
