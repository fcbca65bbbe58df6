"""The work a window did, per request, from the harness's records: what
the roofline and utilization readers divide by time."""
from __future__ import annotations

from bench.kernels import chunk_prefill, model_step, paged_attention


def requests(w):
    """Per request the engine saw in the window: (prompt tokens, first
    prompt position it ran, last, tokens it emitted)."""
    out = []
    for rid, req in w.rec.reqs.items():
        start, end = w.prefill_pairs.get(rid, (0, 0))
        out.append((len(req.tokens), start, end, w.rec.n_seen.get(rid, 0)))
    return out


def _least_s(flops, nbytes, peak):
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def paged_attention_need(w, peak):
    """(least seconds, flops, bytes) of paged attention over the window's
    decode tokens, every layer. Memory bound at every length: a query token
    does 2 * heads / kv_heads FLOPs per byte of K and V."""
    conf = w.cell.config
    t = f = b = 0.0
    for prompt, _, _, n_out in requests(w):
        if n_out > 1:
            fl, by = paged_attention.decode_cost(conf, prompt, n_out - 1)
            f, b = f + fl, b + by
            t += _least_s(fl, by, peak)
    return t, f, b


def chunk_prefill_need(w, peak):
    """(least seconds, flops, bytes) of chunked-prefill attention over the
    prompt chunks the window ran, every layer, each chunk at its own bound."""
    conf = w.cell.config
    H, Hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    L = conf["num_hidden_layers"]
    chunk = int(conf["serving"]["prefill_chunk_tokens"])
    t = f = b = 0.0
    for _, start, end, _ in requests(w):
        for a, e in chunk_prefill.prompt_chunks(start, end, chunk):
            fl, by = chunk_prefill.cost(H, Hkv, hd, a, e)
            f, b = f + fl * L, b + by * L
            t += _least_s(fl, by, peak) * L
    return t, f, b


def model_flops(w):
    """Model FLOPs of every prompt and decode token the window processed."""
    conf = w.cell.config
    tokens = keys = rows = 0
    for prompt, start, end, n_out in requests(w):
        tokens += end - start
        keys += (end * (end + 1) - start * (start + 1)) // 2
        if n_out:
            rows += n_out
            dec = n_out - 1
            tokens += dec
            keys += dec * prompt + dec * (dec + 1) // 2
    return model_step.flops(conf, tokens, keys, rows)
