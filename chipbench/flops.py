"""Operations and bytes the work of a round requires, from shapes alone.

Model FLOPs of one round (``round_flops``): for every active client u with
``tok_u = min(S_u, s_max) * seq`` real tokens,

* forward: 2 x matmul parameters per token, every projection and the LM
  head over the held vocabulary slice (not the embedding gather, not the
  padded vocabulary columns), plus attention 4 x L x heads x d_head x seq;
* backward: 4 x matmul parameters per token and 8 x heads x d_head x seq of
  attention, counted only through the layers the client's straggler mask
  keeps (the depth-limited backprop of the paper): the blocks whose layer
  is kept, and the head with the last layer.

So attention is 12 x L x heads x d_head x seq per token when every layer
is kept, the usual count that does not halve causal attention.
"""
from __future__ import annotations

import numpy as np


def block_matmul_params(a: dict) -> int:
    """Matmul parameters of one dense GQA + SwiGLU block."""
    D, H, KV = a["d_model"], a["n_heads"], a["n_kv"]
    hd = a.get("d_head") or D // H
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * a["d_ff"]


def head_matmul_params(a: dict) -> int:
    return a["d_model"] * a["vocab"]


def round_flops(a: dict, seq: int, batch: np.ndarray, mask: np.ndarray,
                s_max: int) -> float:
    """Model FLOPs of one round: ``batch`` (U,) planned S_t^u of the active
    clients, ``mask`` (U, L) their straggler masks (column L-1 is the
    output layer, reached first by backprop)."""
    L, H = a["L"], a["n_heads"]
    hd = a.get("d_head") or a["d_model"] // H
    blk, head = block_matmul_params(a), head_matmul_params(a)
    att = H * hd * seq
    tok = np.minimum(np.asarray(batch, np.float64), s_max) * seq      # (U,)
    m = np.asarray(mask, np.float64)
    kept = m.sum(1)                                                   # (U,)
    fwd = 2.0 * (L * blk + head) + 4.0 * L * att
    bwd = 4.0 * (kept * blk + m[:, L - 1] * head) + 8.0 * kept * att
    return float((tok * (fwd + bwd)).sum())

