"""Operations and bytes from shapes, against counts made by hand at one
small shape."""
from bench.kernels import chunk_prefill, model_step, paged_attention

CONF = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 2,
        "vocab_size": 10}


def test_paged_attention_one_token():
    # q.k and p.v: 2 * 2 FLOPs per (head, dim, key): 4 * 4 * 8 * 10
    assert paged_attention.cost(4, 2, 8, 10) == (1280, (320 + 64) * 2)


def test_paged_attention_decode_sums_over_lengths():
    # decode steps 1 and 2 after a 5-token prompt see 6 and 7 keys
    f, b = paged_attention.decode_cost(CONF, 5, 2)
    assert f == 4 * 2 * 4 * 13 * 2
    assert b == (2 * 13 * 1 * 4 + 2 * 2 * 2 * 4) * 2 * 2


def test_chunk_prefill_causal_pairs():
    # positions 2, 3, 4 see 3, 4, 5 keys: 12 pairs
    assert chunk_prefill.cost(4, 2, 8, 2, 5) == (4 * 4 * 8 * 12,
                                                 (160 + 192) * 2)
    assert chunk_prefill.prompt_chunks(16, 600, 256) == [
        (16, 272), (272, 528), (528, 600)]


def test_model_step_flops():
    # per layer: q 8*8, k and v 2*8*4, o 8*8, SwiGLU 3*8*16 = 576 weights
    assert model_step.linear_flops_per_token(CONF) == 2 * 576 * 2
    # 3 tokens, 6 keys attended, 1 row of logits over 10 ids
    assert model_step.flops(CONF, 3, 6, 1) == \
        2304 * 3 + 4 * 2 * 4 * 6 * 2 + 2 * 8 * 10
