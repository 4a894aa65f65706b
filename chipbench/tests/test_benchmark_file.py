"""``BENCHMARK.json`` against the files the harness finds by name."""
import json
import re

import jax
import pytest

from chipbench import families, harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def is_shape(x):
    return isinstance(x, tuple)


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files(cell):
    c = harness.load_cell(cell)
    assert c.limits and set(c.limits) == set(harness.CHECKS)
    # the configuration as run holds the published widths
    a = c.arch
    assert a["d_model"] and a["d_ff"] and a["n_heads"] % a["n_kv"] == 0
    # its family's layer map gives every row of every leaf one mask layer,
    # and every mask layer has its costs
    fam = families.load(a)
    blk, att, _ = fam.costs(a)
    L = len(blk)
    assert len(att) == L
    shapes = jax.tree.leaves(fam.weight_shapes(a), is_leaf=is_shape)
    layers = jax.tree.leaves(fam.layer_map(a), is_leaf=is_shape)
    assert len(shapes) == len(layers)
    seen = set()
    for shape, lay in zip(shapes, layers):
        ids = (lay,) if isinstance(lay, int) else lay
        assert len(ids) == 1 or len(ids) == shape[0], (shape, lay)
        seen |= set(ids)
    assert seen == set(range(L))
    for m in c.per_layer:
        r = harness.reader(m["name"])
        assert (r.LAYER, r.UNIT, r.MOVES) == (m["layer"], m["unit"],
                                              m["moves"])
