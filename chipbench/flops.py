"""Operations and bytes the work of a round requires, from shapes alone.

Model FLOPs of one round (``round_flops``), from the per-token costs the
configuration's family module gives (``costs``): for mask layer l the
block's matmul parameters ``blk[l]`` and attention width ``att[l]``
(H x (d_qk + d_v)), and the head's matmul parameters ``head``. For every
active client u with ``tok_u = min(S_u, s_max) * seq`` real tokens and
straggler mask ``m_u``, per token,

* forward: ``2 (sum_l blk[l] + head) + 2 seq sum_l att[l]``: every
  projection and the head over the held vocabulary slice (not the
  embedding gather, not the padded vocabulary columns), and attention's
  two matmuls over ``seq`` positions;
* backward: ``4 (m_u . blk + m_u[L-1] head) + 4 seq (m_u . att)``,
  counted only through the layers the mask keeps (the depth-limited
  backprop of the paper): the blocks whose layer is kept, and the head
  with the last layer.

So attention is ``6 seq sum_l att[l]`` per token when every layer is
kept, the usual count that does not halve causal attention.
"""
from __future__ import annotations

import numpy as np

from chipbench import families


def round_flops(a: dict, seq: int, batch: np.ndarray, mask: np.ndarray,
                s_max: int) -> float:
    """Model FLOPs of one round: ``batch`` (U,) planned S_t^u of the active
    clients, ``mask`` (U, L) their straggler masks (column L-1 is the
    output layer, reached first by backprop)."""
    blk, att, head = families.load(a).costs(a)
    blk, att = np.asarray(blk, np.float64), np.asarray(att, np.float64)
    L = blk.size
    tok = np.minimum(np.asarray(batch, np.float64), s_max) * seq      # (U,)
    m = np.asarray(mask, np.float64)                                  # (U, L)
    fwd = 2.0 * (blk.sum() + head) + 2.0 * seq * att.sum()
    bwd = 4.0 * (m @ blk + m[:, L - 1] * head) + 4.0 * seq * (m @ att)
    return float((tok * (fwd + bwd)).sum())
