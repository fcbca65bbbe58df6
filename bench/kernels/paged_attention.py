"""Paged decode attention (``repro.kernels.paged_attention``): one query
token attends to ``kv_len`` cached tokens through a block table."""

NAMES = ("paged_attention",)


def cost(n_heads: int, n_kv_heads: int, head_dim: int, kv_len: int,
         dtype_bytes: int = 2):
    """(flops, bytes) of one query token of one layer: q.k and p.v over
    ``kv_len`` keys; K and V read once, q read and the output written."""
    flops = 4 * n_heads * head_dim * kv_len
    nbytes = (2 * kv_len * n_kv_heads * head_dim
              + 2 * n_heads * head_dim) * dtype_bytes
    return flops, nbytes


def decode_cost(conf: dict, prompt_len: int, n_decoded: int):
    """(flops, bytes) summed over every layer and over the ``n_decoded``
    decode tokens of one request whose prompt has ``prompt_len`` tokens:
    decode step k (1..n) attends to prompt_len + k keys."""
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, L = conf["head_dim"], conf["num_hidden_layers"]
    keys = n_decoded * prompt_len + n_decoded * (n_decoded + 1) // 2
    flops = 4 * H * hd * keys * L
    nbytes = (2 * keys * Hkv * hd + 2 * n_decoded * H * hd) * 2 * L
    return flops, nbytes
