"""Generic layered LM backbone covering all assigned architecture families.

One :class:`~repro.configs.base.ArchConfig` selects among:

* dense / vlm  — pre-norm GQA transformer (optional QKV bias, full/half RoPE,
  optional sliding window), SwiGLU FFN.
* moe          — GQA or MLA attention + token-choice top-k MoE FFN
  (optional shared experts, optional Arctic-style dense residual FFN).
* ssm          — Mamba2 SSD blocks (attention-free).
* hybrid       — parallel attention + SSD heads per block (Hymba).
* audio        — encoder-decoder; the encoder consumes stubbed frame
  embeddings, the decoder adds cross-attention.

The L blocks are stored STACKED (leading axis L) and driven by
``jax.lax.scan`` — HLO size is O(1) in depth and ADEL-FL's per-layer
truncation masks become a single broadcast multiply over the stacked axis
(see ``layer_ids``). Forward computation is cast to ``cfg.dtype``
(bf16 on TPU) with float32 softmax/norms; parameters stay in their stored
dtype (f32 for training, bf16 for serving).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops as kernel_ops
from repro.models.attention import (decode_attention, gqa_attention,
                                    mla_decode, mla_prefill, rope)
from repro.models.moe import moe_ffn
from repro.models.ssm import ssd_chunked, ssd_decode_step

PyTree = Any

__all__ = ["init_params", "embed_lookup", "forward", "loss_fn", "init_cache",
           "prefill", "decode_step", "layer_ids", "param_specs",
           "cache_specs", "count_params", "Cache"]


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------

def _rms_norm(x: jnp.ndarray, g: jnp.ndarray, eps: float) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g.astype(x.dtype)


def _dense(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[-2])
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def _swiglu(h, p, compute_dtype):
    wg, wu, wd = (p["wg"].astype(compute_dtype), p["wu"].astype(compute_dtype),
                  p["wd"].astype(compute_dtype))
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


# ---------------------------------------------------------------------------
# parameter init (per-block dicts; stacked over L by the caller)
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ArchConfig, dtype):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense(ks[0], (D, H * hd), dtype=dtype),
        "wk": _dense(ks[1], (D, KV * hd), dtype=dtype),
        "wv": _dense(ks[2], (D, KV * hd), dtype=dtype),
        "wo": _dense(ks[3], (H * hd, D), scale=1.0 / np.sqrt(H * hd), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def _init_mla(key, cfg: ArchConfig, dtype):
    D, H = cfg.d_model, cfg.n_heads
    dn, dr, dv, c = (cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim,
                     cfg.kv_lora)
    ks = jax.random.split(key, 6)
    return {
        "wq": _dense(ks[0], (D, H * (dn + dr)), dtype=dtype),
        "w_dkv": _dense(ks[1], (D, c), dtype=dtype),
        "w_uk": _dense(ks[2], (c, H * dn), dtype=dtype),
        "w_uv": _dense(ks[3], (c, H * dv), dtype=dtype),
        "w_kr": _dense(ks[4], (D, dr), dtype=dtype),
        "wo": _dense(ks[5], (H * dv, D), scale=1.0 / np.sqrt(H * dv), dtype=dtype),
    }


def _init_mlp(key, cfg: ArchConfig, dtype, d_ff=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {"wg": _dense(ks[0], (D, F), dtype=dtype),
            "wu": _dense(ks[1], (D, F), dtype=dtype),
            "wd": _dense(ks[2], (F, D), scale=1.0 / np.sqrt(F), dtype=dtype)}


def _init_moe(key, cfg: ArchConfig, dtype):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": _dense(ks[0], (D, E), dtype=jnp.float32),
        "wg": _dense(ks[1], (E, D, F), dtype=dtype),
        "wu": _dense(ks[2], (E, D, F), dtype=dtype),
        "wd": _dense(ks[3], (E, F, D), scale=1.0 / np.sqrt(F), dtype=dtype),
    }


def _init_ssm(key, cfg: ArchConfig, dtype):
    D, di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    proj_out = 2 * di + 2 * N + H          # z, x, b, c, dt
    conv_ch = di + 2 * N                   # conv over (x, b, c)
    ks = jax.random.split(key, 3)
    return {
        "in_proj": _dense(ks[0], (D, proj_out), dtype=dtype),
        "conv_w": (0.1 * jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch))).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),            # softplus -> ~0.69
        "skip_D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_g": jnp.ones((di,), jnp.float32),
        "out_proj": _dense(ks[2], (di, D), scale=1.0 / np.sqrt(di), dtype=dtype),
    }


def _init_block(key, cfg: ArchConfig, dtype, *, encoder: bool = False):
    """One block's params; the caller vmaps this over L keys to stack."""
    ks = jax.random.split(key, 8)
    p: dict = {"norm1": jnp.ones((cfg.d_model,), jnp.float32)}
    if encoder:
        p["attn"] = _init_attn(ks[0], cfg, dtype)
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["mlp"] = _init_mlp(ks[1], cfg, dtype)
        return p
    if cfg.has_attention:
        p["attn"] = (_init_mla(ks[0], cfg, dtype) if cfg.attention == "mla"
                     else _init_attn(ks[0], cfg, dtype))
    if cfg.has_ssm:
        p["ssm"] = _init_ssm(ks[1], cfg, dtype)
        if cfg.family == "hybrid":
            p["fuse_na"] = jnp.ones((cfg.d_model,), jnp.float32)
            p["fuse_ns"] = jnp.ones((cfg.d_model,), jnp.float32)
    if cfg.enc_layers:                     # decoder cross-attention
        p["norm_x"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["xattn"] = _init_attn(ks[2], cfg, dtype)
    if cfg.is_moe or cfg.has_attention or cfg.family == "hybrid":
        if not (cfg.family == "ssm"):
            p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
    if cfg.is_moe:
        p["moe"] = _init_moe(ks[3], cfg, dtype)
        if cfg.n_shared:
            p["shared"] = _init_mlp(ks[4], cfg, dtype,
                                    d_ff=cfg.n_shared * cfg.expert_d_ff)
        if cfg.dense_residual:
            p["dense"] = _init_mlp(ks[5], cfg, dtype)
    elif cfg.has_attention or cfg.family == "hybrid":
        p["mlp"] = _init_mlp(ks[6], cfg, dtype)
    return p


def init_params(key: jax.Array, cfg: ArchConfig, *,
                dtype: jnp.dtype | None = None) -> PyTree:
    """Full model params. Blocks are stacked over the leading L axis."""
    dtype = dtype or jnp.float32
    k_e, k_b, k_enc, k_h = jax.random.split(key, 4)
    V, D = cfg.padded_vocab, cfg.d_model
    params = {
        "embed": _dense(k_e, (V, D), scale=0.02, dtype=dtype),
        "blocks": jax.vmap(
            lambda k: _init_block(k, cfg, dtype))(jax.random.split(k_b, cfg.L)),
        "final_norm": jnp.ones((D,), jnp.float32),
    }
    if cfg.enc_layers:
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_block(k, cfg, dtype, encoder=True))(
                jax.random.split(k_enc, cfg.enc_layers))
        params["enc_norm"] = jnp.ones((D,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(k_h, (D, V), scale=0.02, dtype=dtype)
    return params


def count_params(params: PyTree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# block forward (full-sequence: train / prefill)
# ---------------------------------------------------------------------------

def _attn_forward(p, cfg: ArchConfig, h, positions, cdt, *, causal=True,
                  kv_override=None):
    """GQA path. h: (B, S, D). kv_override: precomputed (k, v) for cross-attn."""
    B, S, D = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = h @ p["wq"].astype(cdt)
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
    q = q.reshape(B, S, H, hd)
    if kv_override is None:
        k = h @ p["wk"].astype(cdt)
        v = h @ p["wv"].astype(cdt)
        if "bk" in p:
            k = k + p["bk"].astype(cdt)
            v = v + p["bv"].astype(cdt)
        k = k.reshape(B, S, KV, hd)
        v = v.reshape(B, S, KV, hd)
        if cfg.rope_mode != "none":
            q = rope(q, positions, mode=cfg.rope_mode, theta=cfg.rope_theta)
            k = rope(k, positions, mode=cfg.rope_mode, theta=cfg.rope_theta)
    else:
        k, v = kv_override
    out = gqa_attention(q, k, v, causal=causal and kv_override is None,
                        window=cfg.window)
    return out.reshape(B, S, H * hd) @ p["wo"].astype(cdt)


def _ssm_split(p, cfg: ArchConfig, h, cdt):
    """in_proj + split. h (B,S,D) -> z (B,S,di), xbc (B,S,di+2N), dt (B,S,H)."""
    di, N, Hs = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    zxbcdt = h @ p["in_proj"].astype(cdt)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * N]
    dt_raw = zxbcdt[..., di + di + 2 * N:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    return z, xbc, dt


def _ssm_forward(p, cfg: ArchConfig, h, cdt, *, chunk=None):
    chunk = chunk or cfg.ssm_chunk
    """Mamba2 SSD mixer (full sequence). h: (B, S, D) -> (B, S, D)."""
    B, S, D = h.shape
    di, N, Hs, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    z, xbc, dt = _ssm_split(p, cfg, h, cdt)
    # depthwise causal conv over sequence (width cfg.ssm_conv)
    w = p["conv_w"].astype(cdt)                       # (W, C)
    pad = jnp.pad(xbc, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S, :] * w[i][None, None, :]
               for i in range(cfg.ssm_conv))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(cdt))
    x = xbc[..., :di].reshape(B, S, Hs, P)
    b = xbc[..., di:di + N]
    c = xbc[..., di + N:]
    A = jax.nn.softplus(p["A_log"])
    q = chunk
    if S % q:                                          # pad to a chunk multiple
        padS = q - S % q
        x = jnp.pad(x, ((0, 0), (0, padS), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, padS), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, padS), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, padS), (0, 0)))
    y, _ = ssd_chunked(x, dt, A, b, c, chunk=q)
    y = y[:, :S]
    y = y + p["skip_D"].astype(cdt)[None, None, :, None] * x[:, :S]
    y = y.reshape(B, S, di)
    y = _rms_norm(y * jax.nn.silu(z), p["norm_g"], cfg.norm_eps)
    return y @ p["out_proj"].astype(cdt)


def _ffn_forward(p, cfg: ArchConfig, h, cdt, *, dropless: bool = False):
    """Dense SwiGLU or MoE (+ shared / dense-residual). Returns (out, aux).

    ``dropless`` (decode path) sizes expert capacity so no token is dropped —
    single-token batches must match the prefill computation exactly.
    """
    if not cfg.is_moe:
        return _swiglu(h, p["mlp"], cdt), jnp.float32(0.0)
    B, S, D = h.shape
    flat = h.reshape(B * S, D)
    moe_p = {k: v.astype(cdt) if k != "router" else v
             for k, v in p["moe"].items()}
    cf = float(cfg.n_experts) if dropless else cfg.capacity_factor
    out, aux = moe_ffn(flat, moe_p, top_k=cfg.top_k, capacity_factor=cf)
    out = out.reshape(B, S, D)
    if cfg.n_shared:
        out = out + _swiglu(h, p["shared"], cdt)
    if cfg.dense_residual:
        out = out + _swiglu(h, p["dense"], cdt)
    return out, aux


def _block_forward(p, cfg: ArchConfig, h, positions, cdt, *,
                   enc_out=None, causal=True):
    """One decoder block, full sequence. Returns (h, aux)."""
    aux = jnp.float32(0.0)
    x = _rms_norm(h, p["norm1"], cfg.norm_eps)
    if cfg.family == "hybrid":
        a = _attn_forward(p["attn"], cfg, x, positions, cdt, causal=causal)
        s = _ssm_forward(p["ssm"], cfg, x, cdt)
        mix = 0.5 * (_rms_norm(a, p["fuse_na"], cfg.norm_eps)
                     + _rms_norm(s, p["fuse_ns"], cfg.norm_eps))
        h = h + mix
    elif cfg.family == "ssm":
        h = h + _ssm_forward(p["ssm"], cfg, x, cdt)
        return h, aux                                   # Mamba block has no FFN
    elif cfg.attention == "mla":
        out, _ = mla_prefill(x, {k: v.astype(cdt) for k, v in p["attn"].items()},
                             cfg, positions)
        h = h + out
    else:
        h = h + _attn_forward(p["attn"], cfg, x, positions, cdt, causal=causal)
    if enc_out is not None:                             # cross-attention
        xq = _rms_norm(h, p["norm_x"], cfg.norm_eps)
        kv = _cross_kv(p["xattn"], cfg, enc_out, cdt)
        h = h + _attn_forward(p["xattn"], cfg, xq, positions, cdt,
                              kv_override=kv)
    x2 = _rms_norm(h, p["norm2"], cfg.norm_eps)
    out, aux = _ffn_forward(p, cfg, x2, cdt)
    return h + out, aux


def _cross_kv(p, cfg: ArchConfig, enc_out, cdt):
    B, Se, D = enc_out.shape
    KV, hd = cfg.n_kv, cfg.head_dim
    k = (enc_out @ p["wk"].astype(cdt)).reshape(B, Se, KV, hd)
    v = (enc_out @ p["wv"].astype(cdt)).reshape(B, Se, KV, hd)
    if "bk" in p:
        k = k + p["bk"].astype(cdt).reshape(KV, hd)
        v = v + p["bv"].astype(cdt).reshape(KV, hd)
    return k, v


def _run_encoder(params, cfg: ArchConfig, frames, cdt):
    """Bidirectional encoder over frame embeddings (B, S_enc, D)."""
    h = frames.astype(cdt)
    positions = jnp.arange(h.shape[1])

    def body(h, p):
        h, _ = _block_forward(p, cfg, h, positions, cdt, causal=False)
        return h, None

    h, _ = jax.lax.scan(body, h, params["enc_blocks"],
                        unroll=bool(cfg.unroll_layers))
    return _rms_norm(h, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# model forward / loss (train & prefill)
# ---------------------------------------------------------------------------

def embed_lookup(table: jnp.ndarray, tokens: jnp.ndarray,
                 dtype) -> jnp.ndarray:
    """``table[tokens]`` cast to ``dtype``: the rows are gathered from the
    master table and then cast, which equals casting the whole table first.
    The table's gradient is the float32 sum of the cotangent rows per token
    (``kernel_ops.embed_table_grad``), not a scatter-add in ``dtype``."""
    return _embed_lookup(table, tokens, jnp.dtype(dtype),
                         (table.shape[0], jnp.dtype(table.dtype)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _embed_lookup(table, tokens, dtype, table_spec):
    return table[tokens].astype(dtype)


def _embed_lookup_fwd(table, tokens, dtype, table_spec):
    return table[tokens].astype(dtype), tokens


def _embed_lookup_bwd(dtype, table_spec, tokens, g):
    num_rows, table_dtype = table_spec
    grad = kernel_ops.embed_table_grad(tokens.reshape(-1),
                                       g.reshape(-1, g.shape[-1]), num_rows)
    return grad.astype(table_dtype), None


_embed_lookup.defvjp(_embed_lookup_fwd, _embed_lookup_bwd)


def forward(params: PyTree, cfg: ArchConfig, tokens: jnp.ndarray, *,
            frontend: jnp.ndarray | None = None, remat: bool = False):
    """Logits for a full sequence.

    tokens: (B, S_text) int32. ``frontend``: (B, n_front, D) patch/frame
    embeddings — prepended for vlm, encoder input for audio.
    Returns (logits (B, S_out, V), aux) with S_out = n_front + S_text for
    vlm, S_text otherwise.
    """
    cdt = jnp.dtype(cfg.dtype)
    h = embed_lookup(params["embed"], tokens, cdt)
    enc_out = None
    if cfg.frontend == "vision" and frontend is not None:
        h = jnp.concatenate([frontend.astype(cdt), h], axis=1)
    elif cfg.frontend == "audio" and frontend is not None:
        enc_out = _run_encoder(params, cfg, frontend, cdt)
    positions = jnp.arange(h.shape[1])

    def body(carry, p):
        h, aux = carry
        h, a = _block_forward(p, cfg, h, positions, cdt, enc_out=enc_out)
        return (h, aux + a), None

    blk = jax.checkpoint(body) if remat else body
    (h, aux), _ = jax.lax.scan(blk, (h, jnp.float32(0.0)), params["blocks"],
                               unroll=bool(cfg.unroll_layers))
    h = _rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cdt)
    logits = h @ head
    return logits, aux


def loss_fn(params: PyTree, cfg: ArchConfig, tokens: jnp.ndarray,
            labels: jnp.ndarray, *, frontend: jnp.ndarray | None = None,
            moe_aux_coef: float = 0.01, remat: bool = False) -> jnp.ndarray:
    """Mean next-token CE over the text segment (+ MoE load-balance aux)."""
    logits, aux = forward(params, cfg, tokens, frontend=frontend, remat=remat)
    if cfg.frontend == "vision" and frontend is not None:
        logits = logits[:, frontend.shape[1]:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    if cfg.is_moe:
        loss = loss + moe_aux_coef * aux / cfg.L
    return loss


# ---------------------------------------------------------------------------
# KV / SSM / conv caches + decode
# ---------------------------------------------------------------------------

class Cache(NamedTuple):
    kv: Optional[tuple] = None        # (k, v): (L, B, W_or_S, KV, hd)
    mla: Optional[tuple] = None       # (c_kv (L,B,S,c), k_pe (L,B,S,dr))
    ssm: Optional[tuple] = None       # (state (L,B,H,N,P), conv (L,B,W-1,C))
    cross: Optional[tuple] = None     # (k, v): (L, B, S_enc, KV, hd)


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, *,
               dtype=None, enc_out: jnp.ndarray | None = None) -> Cache:
    dtype = dtype or jnp.dtype(cfg.dtype)
    L, KV, hd = cfg.L, cfg.n_kv, cfg.head_dim
    kv = mla = ssm = cross = None
    if cfg.has_attention:
        if cfg.attention == "mla":
            mla = (jnp.zeros((L, batch, max_seq, cfg.kv_lora), dtype),
                   jnp.zeros((L, batch, max_seq, cfg.mla_rope_dim), dtype))
        else:
            W = min(cfg.window, max_seq) if cfg.window else max_seq
            kv = (jnp.zeros((L, batch, W, KV, hd), dtype),
                  jnp.zeros((L, batch, W, KV, hd), dtype))
    if cfg.has_ssm:
        ssm = (jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_state,
                          cfg.ssm_head_dim), jnp.float32),
               jnp.zeros((L, batch, cfg.ssm_conv - 1,
                          cfg.d_inner + 2 * cfg.ssm_state), dtype))
    return Cache(kv=kv, mla=mla, ssm=ssm, cross=cross)


def build_cross_cache(params: PyTree, cfg: ArchConfig,
                      enc_out: jnp.ndarray) -> tuple:
    """Precompute per-layer cross-attention K/V from the encoder output
    (decode then never re-projects the encoder states)."""
    cdt = jnp.dtype(cfg.dtype)

    def body(_, p):
        return None, _cross_kv(p["xattn"], cfg, enc_out, cdt)

    _, (ks, vs) = jax.lax.scan(body, None, params["blocks"],
                               unroll=bool(cfg.unroll_layers))
    return (ks, vs)


def _attn_decode(p, cfg: ArchConfig, x, pos, kv_l, cdt, cross=False,
                 n_valid=None):
    """Single-token GQA decode for one layer. x: (B, 1, D)."""
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = x @ p["wq"].astype(cdt)
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
    q = q.reshape(B, 1, H, hd)
    k_c, v_c = kv_l
    if cross:
        nv = k_c.shape[1] if n_valid is None else n_valid
        out = decode_attention(q, k_c, v_c, nv)
        return (out.reshape(B, 1, H * hd) @ p["wo"].astype(cdt)), kv_l
    k = x @ p["wk"].astype(cdt)
    v = x @ p["wv"].astype(cdt)
    if "bk" in p:
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    k = k.reshape(B, 1, KV, hd)
    v = v.reshape(B, 1, KV, hd)
    if cfg.rope_mode != "none":
        pvec = jnp.full((1,), pos)
        q = rope(q, pvec, mode=cfg.rope_mode, theta=cfg.rope_theta)
        k = rope(k, pvec, mode=cfg.rope_mode, theta=cfg.rope_theta)
    W = k_c.shape[1]
    slot = (pos % W) if cfg.window else jnp.minimum(pos, W - 1)
    k_c = jax.lax.dynamic_update_slice_in_dim(k_c, k.astype(k_c.dtype), slot, 1)
    v_c = jax.lax.dynamic_update_slice_in_dim(v_c, v.astype(v_c.dtype), slot, 1)
    n_valid = jnp.minimum(pos + 1, W)
    out = decode_attention(q, k_c, v_c, n_valid)
    return (out.reshape(B, 1, H * hd) @ p["wo"].astype(cdt)), (k_c, v_c)


def _ssm_decode(p, cfg: ArchConfig, x, ssm_l, cdt):
    """Single-token SSD decode for one layer. x: (B, 1, D)."""
    B = x.shape[0]
    di, N, Hs, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    state, conv_hist = ssm_l                       # (B,H,N,P), (B,W-1,C)
    z, xbc, dt = _ssm_split(p, cfg, x, cdt)        # (B,1,·)
    seq = jnp.concatenate([conv_hist, xbc], axis=1)           # (B, W, C)
    w = p["conv_w"].astype(cdt)
    conv = jnp.einsum("bwc,wc->bc", seq, w) + p["conv_b"].astype(cdt)
    xbc1 = jax.nn.silu(conv)
    xh = xbc1[:, :di].reshape(B, Hs, P)
    b = xbc1[:, di:di + N]
    c = xbc1[:, di + N:]
    A = jax.nn.softplus(p["A_log"])
    y, state = ssd_decode_step(xh, dt[:, 0], A, b, c, state)
    y = y + p["skip_D"].astype(cdt)[None, :, None] * xh
    y = y.reshape(B, di)
    y = _rms_norm(y * jax.nn.silu(z[:, 0]), p["norm_g"], cfg.norm_eps)
    out = (y @ p["out_proj"].astype(cdt))[:, None, :]
    return out, (state, seq[:, 1:])


def decode_step(params: PyTree, cfg: ArchConfig, cache: Cache,
                token: jnp.ndarray, pos: jnp.ndarray):
    """One decode step. token: (B,) int32; pos: scalar int32 (absolute).

    Returns (logits (B, V), new_cache).
    """
    cdt = jnp.dtype(cfg.dtype)
    h = params["embed"].astype(cdt)[token][:, None, :]          # (B, 1, D)

    def body(h, xs):
        p, kv_l, mla_l, ssm_l, cross_l = xs
        x = _rms_norm(h, p["norm1"], cfg.norm_eps)
        new_kv, new_mla, new_ssm = kv_l, mla_l, ssm_l
        if cfg.family == "hybrid":
            a, new_kv = _attn_decode(p["attn"], cfg, x, pos, kv_l, cdt)
            s, new_ssm = _ssm_decode(p["ssm"], cfg, x, ssm_l, cdt)
            mix = 0.5 * (_rms_norm(a, p["fuse_na"], cfg.norm_eps)
                         + _rms_norm(s, p["fuse_ns"], cfg.norm_eps))
            h = h + mix
        elif cfg.family == "ssm":
            out, new_ssm = _ssm_decode(p["ssm"], cfg, x, ssm_l, cdt)
            return h + out, (new_kv, new_mla, new_ssm)
        elif cfg.attention == "mla":
            c_c, pe_c = mla_l
            ap = {k: v.astype(cdt) for k, v in p["attn"].items()}
            # append this token's compressed kv
            c_new = x[:, 0] @ ap["w_dkv"]
            pe_new = rope((x @ ap["w_kr"])[:, :, None, :],
                          jnp.full((1,), pos), mode="full",
                          theta=cfg.rope_theta)[:, 0, 0]
            c_c = jax.lax.dynamic_update_slice_in_dim(
                c_c, c_new[:, None].astype(c_c.dtype), pos, 1)
            pe_c = jax.lax.dynamic_update_slice_in_dim(
                pe_c, pe_new[:, None].astype(pe_c.dtype), pos, 1)
            out = mla_decode(x, ap, cfg, c_c, pe_c, pos)
            h = h + out
            new_mla = (c_c, pe_c)
        else:
            out, new_kv = _attn_decode(p["attn"], cfg, x, pos, kv_l, cdt)
            h = h + out
        if cross_l is not None:
            xq = _rms_norm(h, p["norm_x"], cfg.norm_eps)
            out, _ = _attn_decode(p["xattn"], cfg, xq, pos, cross_l, cdt,
                                  cross=True)
            h = h + out
        if "norm2" in p:
            x2 = _rms_norm(h, p["norm2"], cfg.norm_eps)
            out, _ = _ffn_forward(p, cfg, x2, cdt, dropless=True)
            h = h + out
        return h, (new_kv, new_mla, new_ssm)

    xs = (params["blocks"], cache.kv, cache.mla, cache.ssm, cache.cross)
    # scan requires every xs leaf to have leading L; None entries are passed
    # through a custom scan via masking — simplest is to substitute dummies.
    h, new_layers = _scan_with_optional(body, h, xs, cfg.L,
                                        unroll=bool(cfg.unroll_layers))
    new_kv, new_mla, new_ssm = new_layers
    h = _rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cdt)
    logits = (h[:, 0] @ head).astype(jnp.float32)
    return logits, Cache(kv=new_kv, mla=new_mla, ssm=new_ssm,
                         cross=cache.cross)


def _scan_with_optional(body, carry, xs, L, *, unroll: bool = False):
    """lax.scan over (blocks, kv, mla, ssm, cross) where some cache groups are
    None. None groups are replaced by per-layer zero-size placeholders."""
    blocks, kv, mla, ssm, cross = xs
    dummy = jnp.zeros((L, 0), jnp.float32)

    def wrap(c, x):
        p, kv_l, mla_l, ssm_l, cross_l, _ = x
        kv_l = None if kv is None else kv_l
        mla_l = None if mla is None else mla_l
        ssm_l = None if ssm is None else ssm_l
        cross_l = None if cross is None else cross_l
        c, (nkv, nmla, nssm) = body(c, (p, kv_l, mla_l, ssm_l, cross_l))
        z = jnp.zeros((0,), jnp.float32)
        return c, (z if nkv is None else nkv, z if nmla is None else nmla,
                   z if nssm is None else nssm)

    sub = lambda g: g if g is not None else dummy
    carry, (nkv, nmla, nssm) = jax.lax.scan(
        wrap, carry, (blocks, sub(kv), sub(mla), sub(ssm), sub(cross), dummy),
        unroll=unroll)
    return carry, (None if kv is None else nkv, None if mla is None else nmla,
                   None if ssm is None else nssm)


def prefill(params: PyTree, cfg: ArchConfig, tokens: jnp.ndarray, *,
            frontend: jnp.ndarray | None = None):
    """Prefill: full-sequence forward returning last-position logits.

    (Cache materialization for the serve path is exercised by ``decode_step``;
    the prefill *compute* — the expensive part — is what prefill shapes lower.)
    """
    logits, _ = forward(params, cfg, tokens, frontend=frontend)
    return logits[:, -1]


# ---------------------------------------------------------------------------
# ADEL layer ids + sharding specs
# ---------------------------------------------------------------------------

def layer_ids(params: PyTree, cfg: ArchConfig) -> PyTree:
    """Pytree congruent with params mapping leaves to ADEL mask layers.

    Blocks get their stacked index (decoder blocks offset by enc_layers —
    backprop reaches the decoder first, so the encoder is 'deeper' / lower
    id). The embedding joins layer 0 (reached last); final norm + head join
    the last layer (reached first).
    """
    Ltot = cfg.n_blocks_total
    ids: dict = {}
    for k, v in params.items():
        if k == "blocks":
            ids[k] = jax.tree.map(
                lambda _: jnp.arange(cfg.L, dtype=jnp.int32) + cfg.enc_layers, v)
        elif k == "enc_blocks":
            ids[k] = jax.tree.map(
                lambda _: jnp.arange(cfg.enc_layers, dtype=jnp.int32), v)
        elif k == "embed":
            ids[k] = jnp.int32(0)
        else:  # final_norm, enc_norm, lm_head
            ids[k] = jax.tree.map(lambda _: jnp.int32(Ltot - 1), v)
    return ids


def param_specs(params: PyTree, cfg: ArchConfig, *, fsdp: str | tuple = "data",
                tp: str = "model") -> PyTree:
    """PartitionSpec tree: 2D (fsdp x tensor) sharding.

    Big matrices shard their input dim over ``fsdp`` (the data axis — ZeRO-3
    style, layers re-gathered one at a time under the scan) and their output/
    feature dim over ``tp``. Vectors/norms replicate. The stacked L axis is
    NEVER sharded (ADEL masks index it).
    """
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = keys[-1] if keys else ""
        nd = leaf.ndim
        if name == "embed":
            return P(tp, fsdp)
        if name == "lm_head":
            return P(fsdp, tp)
        if name == "router":
            return P(None, fsdp, None)
        if name in ("wg", "wu") and nd == 4:          # experts (L, E, D, F)
            return P(None, fsdp, None, tp)
        if name == "wd" and nd == 4:                  # (L, E, F, D)
            return P(None, fsdp, tp, None)
        if name in ("wq", "wk", "wv", "wg", "wu", "w_dkv", "w_uk", "w_uv",
                    "w_kr", "in_proj") and nd == 3:   # (L, D, F)
            return P(None, fsdp, tp)
        if name in ("wo", "wd", "out_proj") and nd == 3:  # (L, F, D)
            return P(None, tp, fsdp)
        if name in ("bq", "bk", "bv") and nd == 2:    # (L, F)
            return P(None, tp)
        return P(*([None] * nd))                      # norms, scalars, conv

    return jax.tree_util.tree_map_with_path(spec, params)


def cache_specs(cache: Cache, cfg: ArchConfig, *, batch="data", tp="model"):
    """Cache sharding: batch over the data axes, head_dim over ``tp`` for KV
    caches (so decode attention reduces over tp with one psum per layer);
    MLA latent dim over ``tp``; SSM state-heads over ``tp``."""
    from jax.sharding import PartitionSpec as P

    def kv_spec(x):
        return P(None, batch, None, None, tp)         # (L,B,S,KV,hd)

    kv = None if cache.kv is None else tuple(kv_spec(x) for x in cache.kv)
    mla = None if cache.mla is None else (
        P(None, batch, None, tp), P(None, batch, None, None))
    ssm = None if cache.ssm is None else (
        P(None, batch, tp, None, None), P(None, batch, None, tp))
    cross = None if cache.cross is None else tuple(
        kv_spec(x) for x in cache.cross)
    return Cache(kv=kv, mla=mla, ssm=ssm, cross=cross)
