"""The ``dense`` family: a uniform stack of pre-norm GQA + SwiGLU blocks.

Layout, as the program keeps its parameters: the blocks stacked over a
leading layer axis, GQA projections with QKV bias where the configuration
has it, SwiGLU, RMSNorm gains, an untied LM head, and the embedding and
head padded to a multiple of 512 vocabulary rows.

Forward: RMSNorm, GQA with QKV bias, RoPE over all or the first half of
each head (rotate-half pairing), causal softmax attention, SwiGLU, an
untied head over the held vocabulary rows as stored (padding included,
as the weights are given).

Mask layers: layer l is block l; the embedding joins layer 0 and the final
norm and LM head join layer L-1 (backprop reaches the output first).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 512


def padded_vocab(a: dict) -> int:
    return -(-a["vocab"] // VOCAB_PAD) * VOCAB_PAD


def _head_dim(a: dict) -> int:
    return a.get("d_head") or a["d_model"] // a["n_heads"]


def weight_shapes(a: dict) -> dict:
    """Leaf shapes of the parameter tree, keyed like the program's."""
    D, H, KV, F, L = a["d_model"], a["n_heads"], a["n_kv"], a["d_ff"], a["L"]
    hd = _head_dim(a)
    V = padded_vocab(a)
    attn = {"wq": (L, D, H * hd), "wk": (L, D, KV * hd),
            "wv": (L, D, KV * hd), "wo": (L, H * hd, D)}
    if a.get("qkv_bias"):
        attn.update(bq=(L, H * hd), bk=(L, KV * hd), bv=(L, KV * hd))
    return {"embed": (V, D),
            "blocks": {"norm1": (L, D), "attn": attn, "norm2": (L, D),
                       "mlp": {"wg": (L, D, F), "wu": (L, D, F),
                               "wd": (L, F, D)}},
            "final_norm": (D,), "lm_head": (D, V)}


def init_leaf(path: tuple, shape: tuple, key):
    """Norm gains ``1 + 0.02 z``, biases, embedding and head ``0.02 z``,
    every other matrix ``z`` over the square root of its fan-in."""
    name = path[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("norm1", "norm2", "final_norm"):
        return 1.0 + 0.02 * z
    if name in ("bq", "bk", "bv", "embed", "lm_head"):
        return 0.02 * z
    return z / np.sqrt(shape[-2])


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, mode: str, theta: float):
    S, hd = x.shape[1], x.shape[-1]
    rot = hd if mode == "full" else hd // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def logits(params, a: dict, tok, mm):
    """(B, S) token ids -> (B, S, V_held) float32 logits."""
    B, S = tok.shape
    H, KV = a["n_heads"], a["n_kv"]
    hd = _head_dim(a)
    G, eps = H // KV, a["norm_eps"]
    h = params["embed"][tok]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for l in range(a["L"]):
        p = jax.tree.map(lambda x: x[l], params["blocks"])
        at = p["attn"]
        x = _rms(h, p["norm1"], eps)
        q = mm("bsd,df->bsf", x, at["wq"])
        k = mm("bsd,df->bsf", x, at["wk"])
        v = mm("bsd,df->bsf", x, at["wv"])
        if "bq" in at:
            q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
        q = _rope(q.reshape(B, S, H, hd), a["rope_mode"], a["rope_theta"])
        k = _rope(k.reshape(B, S, KV, hd), a["rope_mode"], a["rope_theta"])
        k = jnp.repeat(k, G, axis=2)                 # head h reads kv h // G
        v = jnp.repeat(v.reshape(B, S, KV, hd), G, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        s = jnp.where(causal, s, -1e30)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        h = h + mm("bsf,fd->bsd", o.reshape(B, S, H * hd), at["wo"])
        x = _rms(h, p["norm2"], eps)
        ml = p["mlp"]
        f = jax.nn.silu(mm("bsd,df->bsf", x, ml["wg"])) * mm(
            "bsd,df->bsf", x, ml["wu"])
        h = h + mm("bsf,fd->bsd", f, ml["wd"])
    h = _rms(h, params["final_norm"], eps)
    return mm("bsd,dv->bsv", h, params["lm_head"])


def layer_map(a: dict) -> dict:
    """Block rows are layers 0..L-1; the embedding is layer 0, the final
    norm and the head layer L-1."""
    L = a["L"]
    blocks = jax.tree.map(lambda _: tuple(range(L)),
                          weight_shapes(a)["blocks"],
                          is_leaf=lambda x: isinstance(x, tuple))
    return {"embed": 0, "blocks": blocks, "final_norm": L - 1,
            "lm_head": L - 1}


def costs(a: dict) -> tuple:
    """Per token: each block's matmul parameters (GQA projections and
    SwiGLU) and attention width 2 x H x d_head, and the head over the held
    vocabulary (not the embedding gather, not the padded columns)."""
    D, H, KV, L = a["d_model"], a["n_heads"], a["n_kv"], a["L"]
    hd = _head_dim(a)
    blk = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * a["d_ff"]
    return (np.full(L, blk, np.float64), np.full(L, 2 * H * hd, np.float64),
            D * a["vocab"])
