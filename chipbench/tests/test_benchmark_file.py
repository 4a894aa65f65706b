"""``BENCHMARK.json`` against the files the harness finds by name."""
import json
import re

import pytest

from chipbench import harness, inputs

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


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
    assert inputs.weight_shapes(a)["blocks"]["mlp"]["wg"] == (
        a["L"], a["d_model"], a["d_ff"])
    for m in c.per_layer:
        r = harness.reader(m["name"])
        assert (r.LAYER, r.UNIT, r.MOVES) == (m["layer"], m["unit"],
                                              m["moves"])
