"""Model programs: real prompt tokens over the positions the chunk programs
computed, from the engine's own counters over the window: every launch
computes ``max_slots`` x ``prefill_chunk_tokens`` positions however many
prompts advance, so the rest is padding."""


def read(w):
    # a program from before the counters reads nothing; the program's own
    # tests (tests/test_telemetry.py) hold the counters' names
    if "engine_chunk_launches" not in w.stats1[0]:
        return None
    launches = sum(b["engine_chunk_launches"] - a["engine_chunk_launches"]
                   for a, b in zip(w.stats0, w.stats1))
    tokens = sum(b["engine_chunk_tokens"] - a["engine_chunk_tokens"]
                 for a, b in zip(w.stats0, w.stats1))
    srv = w.cell.serving
    slots = launches * int(srv["max_slots"]) \
        * int(srv["prefill_chunk_tokens"])
    return 100.0 * tokens / slots if slots else None
