"""The FLOP and byte counts against counts made by hand."""
import numpy as np

from chipbench import families, flops

# d_model 8, 2 query heads and 1 KV head of 4, d_ff 16, vocab 10, L 2
A = {"family": "dense", "L": 2, "d_model": 8, "n_heads": 2, "n_kv": 1,
     "d_head": 4, "d_ff": 16, "vocab": 10}


def test_block_and_head_params():
    blk, att, head = families.load(A).costs(A)
    # wq 8x8 + wk, wv 8x4 each + wo 8x8 + 3 x 8x16, in each of the 2 layers
    assert list(blk) == [64 + 32 + 32 + 64 + 384] * 2
    # 2 heads x (d_qk 4 + d_v 4)
    assert list(att) == [2 * (4 + 4)] * 2
    assert head == 80


def test_round_flops_every_layer_kept():
    seq = 3
    # one client, 2 real rows of 3 tokens; every layer kept
    got = flops.round_flops(A, seq, np.array([2]), np.ones((1, 2)), s_max=4)
    blk, head, att = 576, 80, 2 * 4 * 3
    per_tok = (2 * (2 * blk + head) + 4 * 2 * att
               + 4 * (2 * blk + head) + 8 * 2 * att)
    assert got == 6 * per_tok
    # attention is 12 x L x heads x d_head x seq per token when all is kept
    assert 4 * 2 * att + 8 * 2 * att == 12 * 2 * 2 * 4 * 3


def test_round_flops_mask_and_cap():
    seq = 3
    # batch 9 capped at s_max 4; only the output layer kept (column L-1)
    got = flops.round_flops(A, seq, np.array([9]), np.array([[0.0, 1.0]]),
                            s_max=4)
    blk, head, att = 576, 80, 24
    per_tok = 2 * (2 * blk + head) + 4 * 2 * att + 4 * (blk + head) + 8 * att
    assert got == 12 * per_tok
    # a client that finished no layer does the forward only
    got0 = flops.round_flops(A, seq, np.array([1]), np.zeros((1, 2)),
                             s_max=4)
    assert got0 == 3 * (2 * (2 * blk + head) + 4 * 2 * att)

