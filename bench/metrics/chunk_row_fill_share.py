"""Model programs: real prompt tokens over the positions the chunk programs
computed, from the engine's own counters over the window, where a launch
computes as many lanes as it launched (``engine_chunk_lanes``, each
launch's width) x ``prefill_chunk_tokens`` positions: the share of the
launched lanes that is not padding."""


def read(w):
    # a program that launches every chunk max_slots wide has no lane
    # counter, and reads nothing here (chunk_fill_share covers it)
    if "engine_chunk_lanes" not in w.stats1[0]:
        return None
    lanes = sum(b["engine_chunk_lanes"] - a["engine_chunk_lanes"]
                for a, b in zip(w.stats0, w.stats1))
    tokens = sum(b["engine_chunk_tokens"] - a["engine_chunk_tokens"]
                 for a, b in zip(w.stats0, w.stats1))
    positions = lanes * int(w.cell.serving["prefill_chunk_tokens"])
    return 100.0 * tokens / positions if positions else None
