"""What a run feeds the program, made from ``--seed`` alone: the weights
(on the device, in one jitted call, float32 as they are trained) and the
token rows (uniform over the held vocabulary slice).

The weights use the program's parameter layout, which the configuration's
family module (``families/<family>.py``) writes down with the rule that
draws each leaf; the harness checks it against the program's own shapes.
"""
from __future__ import annotations

import functools
import json

import numpy as np

from chipbench import families


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` (64-bit seeds fold in their high
    word)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _weights_fn(arch: str):
    import jax
    a = json.loads(arch)
    fam = families.load(a)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        fam.weight_shapes(a), is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(flat))
        leaves = [fam.init_leaf(tuple(k.key for k in path), shape, kk)
                  for (path, shape), kk in zip(flat, keys)]
        return jax.tree.unflatten(tree, leaves)

    return jax.jit(make)


def make_weights(a: dict, seed: int):
    """The run's float32 weights, on the default device."""
    import jax
    key = jax.random.fold_in(seed_key(seed), 0)
    return _weights_fn(json.dumps(a, sort_keys=True))(key)


def make_tokens(a: dict, traffic: dict, seed: int):
    """(client rows (U, n, seq+1), eval rows (n_eval, seq+1)), int32,
    uniform over the held vocabulary slice."""
    rng = np.random.default_rng([seed, 1])
    U, n, seq = traffic["clients"], traffic["rows_per_client"], traffic["seq"]
    rows = rng.integers(0, a["vocab"], size=(U, n, seq + 1), dtype=np.int32)
    ev = rng.integers(0, a["vocab"], size=(traffic["eval_rows"], seq + 1),
                      dtype=np.int32)
    return rows, ev
