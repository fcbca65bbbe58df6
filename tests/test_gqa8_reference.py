"""Qwen3 at GQA group 8 (qwen3-32b's 64 query heads over 8 KV heads, here
16 over 2 at test widths) through the engine's served path, chunked
prefill then paged one-token decodes, against the benchmark's plain
float32 reference (``bench/reference.py``), logit by logit.

The program serves in bfloat16 (weights, activations and KV), as the
configuration states. Readings at these widths (seeds 1-3, either
attention backend): the served logits lie within 0.021-0.027 of the
float32 reference's, on logits of magnitude up to 4; the same forward
with every linear layer in float8 (e4m3, the reference's control) lies
0.25-0.30 away. ``TOL`` sits between, with room on both sides: bfloat16
rounding passes, one precision lower does not, and a wrong head group,
page or position moves logits by far more than either."""
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness as H, reference, weights as W  # noqa: E402
from repro.core.runtime.accounting import MemoryAccountant  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import engine as engine_mod  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402

VOCAB = 1000
CONF = {"program_config": "qwen3-32b", "hidden_size": 128,
        "intermediate_size": 256, "num_attention_heads": 16,
        "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
        "vocab_size": VOCAB, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
        "attention_bias": False, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16"}
TOL = 0.08


def _reference_rows(params, seq, rows, precision):
    """The reference's logits over the published vocabulary at ``rows`` of
    the token sequence ``seq`` (padded to the reference's query block)."""
    toks = np.zeros(reference.Q_BLOCK, np.int32)
    toks[:len(seq)] = seq
    hs = reference._hidden(CONF, precision, params, jnp.asarray(toks))[rows]
    return np.asarray(reference._mm(hs, params["lm_head"], precision)
                      )[:, :VOCAB]


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_group8_engine_matches_reference(backend, monkeypatch):
    if backend == "pallas":
        # the Pallas kernels in interpret mode, as the engine binds them
        for mod, name in ((engine_mod._cp, "chunk_prefill_attention"),
                          (engine_mod._pa, "paged_attention")):
            monkeypatch.setattr(mod, name, functools.partial(
                getattr(mod, name), interpret=True))
    model = build_model(H.arch_config(CONF))
    assert model.cfg.n_heads // model.cfg.n_kv_heads == 8
    params = W.draw(model.abstract_params(), 1, VOCAB, jax.devices()[0])
    eng = Engine(model, params, MemoryAccountant(m_total=1e9), max_slots=2,
                 s_max=128, kv_backend=backend, prefill_chunk_tokens=16,
                 decode_horizon=1)
    rng = np.random.default_rng(1)
    # prompts of 3 and 4 chunks, prefilling side by side
    reqs = [Request(req_id=i, tokens=[int(t) for t in
                                      rng.integers(0, VOCAB, n)],
                    max_new=8, logits=[]) for i, n in enumerate((37, 50))]
    for r in reqs:
        eng.submit(r)
    eng.drain()
    assert eng.stat_chunk_launches >= 4 and eng.stat_decode_tokens > 0
    for r in reqs:
        seq = r.tokens + r.out[:-1]
        rows = np.arange(len(r.tokens) - 1, len(seq))
        got = np.stack([np.asarray(x) for x in r.logits])[:, :VOCAB]
        assert got.shape[0] == len(rows) == len(r.out) == 8
        want = _reference_rows(params, seq, rows, "f32")
        assert np.abs(got - want).max() < TOL
        # each served token is the reference's best, or within TOL of it
        served = want[np.arange(len(rows)), r.out]
        assert (want.max(axis=1) - served).max() < TOL
        # the float8 control at the same positions lies beyond TOL
        control = _reference_rows(params, seq, rows, "fp8")
        assert np.abs(control - want).max() > 2 * TOL
