"""Model FLOPs of the tokens a serving window processed, for ``step_mfu``.

Per token and layer: the q, k, v and output projections and the three
SwiGLU matrices, 2 FLOPs per weight; attention's q.k and p.v, 4 * heads *
head_dim per visible key; and for every row whose logits are computed
(each decode token, and the last token of each prompt) the LM head over the
published vocabulary. Embedding lookups and norms are not counted."""


def linear_flops_per_token(conf: dict) -> int:
    D, F = conf["hidden_size"], conf["intermediate_size"]
    H, Hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    per_layer = D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F
    return 2 * per_layer * conf["num_hidden_layers"]


def flops(conf: dict, tokens: int, attn_keys: int, logit_rows: int) -> int:
    """``tokens`` processed (prompt and decode), ``attn_keys`` the keys
    they attended to in all (summed over tokens), ``logit_rows`` rows of the
    LM head."""
    attn = (4 * conf["num_attention_heads"] * conf["head_dim"] * attn_keys
            * conf["num_hidden_layers"])
    head = 2 * conf["hidden_size"] * conf["vocab_size"] * logit_rows
    return linear_flops_per_token(conf) * tokens + attn + head
