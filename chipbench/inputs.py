"""What a run feeds the program, made from ``--seed`` alone: the weights
(on the device, in one jitted call, float32 as they are trained) and the
token rows (uniform over the held vocabulary slice).

The weights use the program's parameter layout, which the benchmark
writes down itself from the configuration (the harness checks it against
the program's own shapes): stacked blocks over a leading layer axis, GQA
projections with QKV bias, SwiGLU, RMSNorm gains, an untied LM head, and
the embedding and head padded to a multiple of 512 vocabulary rows.
"""
from __future__ import annotations

import functools

import numpy as np

VOCAB_PAD = 512


def padded_vocab(a: dict) -> int:
    return -(-a["vocab"] // VOCAB_PAD) * VOCAB_PAD


def weight_shapes(a: dict) -> dict:
    """Leaf shapes of the parameter tree, keyed like the program's."""
    D, H, KV, F, L = a["d_model"], a["n_heads"], a["n_kv"], a["d_ff"], a["L"]
    hd = a.get("d_head") or D // H
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


def _leaf(name: str, shape: tuple, key):
    import jax
    import jax.numpy as jnp
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("norm1", "norm2", "final_norm"):
        return 1.0 + 0.02 * z
    if name in ("bq", "bk", "bv", "embed", "lm_head"):
        return 0.02 * z
    return z / np.sqrt(shape[-2])                  # fan-in scaled matrix


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` (64-bit seeds fold in their high
    word)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _weights_fn(arch_items: tuple):
    import jax
    a = dict(arch_items)
    shapes = weight_shapes(a)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(flat))
        leaves = [_leaf(path[-1].key, shape, k)
                  for (path, shape), k in zip(flat, keys)]
        return jax.tree.unflatten(tree, leaves)

    return jax.jit(make)


def make_weights(a: dict, seed: int):
    """The run's float32 weights, on the default device."""
    import jax
    key = jax.random.fold_in(seed_key(seed), 0)
    return _weights_fn(tuple(sorted(a.items())))(key)


def make_tokens(a: dict, traffic: dict, seed: int):
    """(client rows (U, n, seq+1), eval rows (n_eval, seq+1)), int32,
    uniform over the held vocabulary slice."""
    rng = np.random.default_rng([seed, 1])
    U, n, seq = traffic["clients"], traffic["rows_per_client"], traffic["seq"]
    rows = rng.integers(0, a["vocab"], size=(U, n, seq + 1), dtype=np.int32)
    ev = rng.integers(0, a["vocab"], size=(traffic["eval_rows"], seq + 1),
                      dtype=np.int32)
    return rows, ev
