"""The plain reference: a float32 Qwen3 forward in ``jax.numpy``.

It follows the published Qwen3 decoder (Hugging Face ``Qwen3ForCausalLM``):
RMSNorm before attention and MLP, q/k/v projections without bias, RMSNorm
over each head of q and k, rotary embedding on the two halves of each head
(theta from the config), grouped-query attention with a causal mask, a SwiGLU
MLP, a final RMSNorm and an untied LM head. It imports nothing of the
program and has no cache, pages, chunks or batching of its own. It reads
the weights by the names of the program's parameter layout
(``groups/slot0/attn/wq`` ...), the weights that ``bench/weights.py`` drew.

Every matrix product runs in float32 at ``Precision.HIGHEST`` (on a TPU a
float32 product is otherwise rounded to bfloat16). It runs one request at a
time and one layer at a time, upcasting that layer's bfloat16 weights, and
attends in blocks of query rows, so that it fits beside the served model's
weights on one chip.

The control (``precision="fp8"``) is the same forward with every linear
layer computed in float8 (e4m3): weights scaled per output column and
activations per row, as a W8A8 float8 serving path would, products summed
in float32. It is the lower precision a later change could be tempted by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block
V_BLOCKS = 16          # the LM head is applied in this many column blocks
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _mm(x, w, precision):
    """x [..., K] @ w [K, N] in float32; ``fp8`` quantizes both first."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / FP8_MAX
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / FP8_MAX
        sx = jnp.where(sx > 0, sx, 1.0)
        sw = jnp.where(sw > 0, sw, 1.0)
        x = (x / sx).astype(FP8).astype(jnp.float32) * sx
        w = (w / sw).astype(FP8).astype(jnp.float32) * sw
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, heads, hd]; rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA. q [T, H, hd]; k, v [T, Hkv, hd] -> [T, H*hd]."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    nb = T // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, Hkv, g, hd)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k, precision=HIGHEST)
        s = s * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    o = lax.map(block, (jnp.arange(nb), qb))
    return o.reshape(T, H * hd)


def _layer(cfg, precision, x, lp, pos):
    """One decoder layer over one sequence x [T, D] (float32)."""
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    a, f = lp["attn"], lp["ffn"]
    T = x.shape[0]
    h = _rms(x, a["ln"], eps)
    q = _mm(h, a["wq"], precision).reshape(T, H, hd)
    k = _mm(h, a["wk"], precision).reshape(T, Hkv, hd)
    v = _mm(h, a["wv"], precision).reshape(T, Hkv, hd)
    q = _rope(_rms(q, a["qn"], eps), pos, cfg["rope_theta"])
    k = _rope(_rms(k, a["kn"], eps), pos, cfg["rope_theta"])
    x = x + _mm(_attention(q, k, v), a["wo"], precision)
    h = _rms(x, f["ln"], eps)
    gate = _mm(h, f["w_gate"], precision)
    up = _mm(h, f["w_up"], precision)
    return x + _mm(jax.nn.silu(gate) * up, f["w_down"], precision)


def _hidden(cfg, precision, params, tokens):
    """Final-normed hidden states [T, D] of one token sequence."""
    x = params["embed"][tokens].astype(jnp.float32)
    pos = jnp.arange(tokens.shape[0])

    def body(x, lp):
        return _layer(cfg, precision, x, lp["slot0"], pos), None

    x, _ = lax.scan(body, x, params["groups"])
    return _rms(x, params["final_ln"], cfg["rms_norm_eps"])


def _head(cfg, precision, params, hs, pick):
    """Over the published vocabulary, for rows hs [K, D]: the best logit,
    the token that has it, and the logit of token ``pick`` [K] (-1: none)."""
    W = params["lm_head"]
    D, Vp = W.shape
    vocab = cfg["vocab_size"]
    bs = Vp // V_BLOCKS
    Wb = W.reshape(D, V_BLOCKS, bs).transpose(1, 0, 2)
    K = hs.shape[0]

    def block(carry, args):
        best, arg, at = carry
        j, w = args
        lg = _mm(hs, w, precision)                     # [K, bs]
        col = j * bs + jnp.arange(bs)
        lg = jnp.where(col[None, :] < vocab, lg, -jnp.inf)
        m = jnp.max(lg, axis=-1)
        am = j * bs + jnp.argmax(lg, axis=-1)
        arg = jnp.where(m > best, am, arg)
        best = jnp.maximum(best, m)
        hit = col[None, :] == pick[:, None]
        at = jnp.where(jnp.any(hit, -1), jnp.sum(jnp.where(hit, lg, 0.0), -1),
                       at)
        return (best, arg, at), None

    init = (jnp.full((K,), -jnp.inf), jnp.zeros((K,), jnp.int32),
            jnp.full((K,), -jnp.inf))
    (best, arg, at), _ = lax.scan(block, init, (jnp.arange(V_BLOCKS), Wb))
    return best, arg, at


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _run(params, tokens, rows, pick, *, cfg_items, precision):
    cfg = dict(cfg_items)

    def one(args):
        toks, r, pk = args
        hs = _hidden(cfg, precision, params, toks)[r]
        return _head(cfg, precision, params, hs, pk)

    return lax.map(one, (tokens, rows, pick))


def logits_at(cfg: dict, params, tokens, rows, pick, precision="f32"):
    """For each request n and each k: the reference's best logit, its token,
    and the logit of token ``pick[n, k]``, at position ``rows[n, k]`` of the
    token sequence ``tokens[n]``.

    tokens [N, T] int32, T a multiple of ``Q_BLOCK`` (pad at the end: the
    causal mask keeps padding out of earlier positions); rows, pick [N, K]
    int32. Returns three [N, K] arrays: best, argmax, picked."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "vocab_size")
    items = tuple((k, cfg[k]) for k in keys)
    return _run(params, tokens, rows, pick, cfg_items=items,
                precision=precision)
