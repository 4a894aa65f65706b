"""The model description of each configuration family, one module a family.

A configuration's ``arch["family"]`` names its module,
``families/<family>.py``. The module writes down all that the benchmark
knows of the family's parameter layout and equations, and imports nothing
of the program, so that the reference stays independent of it:

* ``weight_shapes(a)``: the leaf shapes of the program's parameter tree
  (a dict whose leaves are shape tuples);
* ``init_leaf(path, shape, key)``: the float32 initial value of the leaf
  at ``path`` (its tuple of keys), drawn from ``key``;
* ``logits(params, a, tok, mm)``: the plain float32 forward pass,
  ``(B, S)`` token ids to ``(B, S, V)`` logits, every matmul as
  ``mm(einsum_equation, x, y)``;
* ``layer_map(a)``: the straggler-mask layers of each leaf, in a tree like
  ``weight_shapes(a)``: an ``int`` for a leaf wholly in one layer, a tuple
  of layer ids, one per row of its leading axis, for a stacked leaf;
* ``costs(a)``: ``(blk, att, head)``: per token, the matmul parameters of
  each mask layer's block and its attention width, H x (d_qk + d_v), as
  ``(L,)`` arrays, and the matmul parameters of the output head.

A configuration of another family joins the benchmark with a new module
here and its data files; no shared module names a leaf.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

DIR = Path(__file__).resolve().parent


def load(a: dict):
    """The family module of the configuration ``a``."""
    path = DIR / f"{a['family']}.py"
    if not path.is_file():
        raise SystemExit(f"chipbench: no model description for family "
                         f"{a['family']!r}: add {path}")
    return _load(str(path))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench.families.{Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
