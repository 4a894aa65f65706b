"""The shard_map round over four devices: it matches the reference, and a
round whose devices leave out their exchange makes ``correct`` false.

Runs in a child process, which gives the CPU four devices before JAX
starts (``--xla_force_host_platform_device_count``).
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import CELL, ROOT

CHILD = r"""
import json, sys
root, bench_file, data_dir, broken = json.loads(sys.argv[1])
sys.path[:0] = [root, root + "/src"]
import jax
assert len(jax.devices()) == 4, jax.devices()
if broken:
    import repro.core.aggregation as agg
    import repro.fl.backends as backends

    def local_only(grads, ids, mask, p, axis_name, *, bias_correct=True):
        # the counts are exchanged, the folded deltas are not
        counts = jax.lax.psum(mask.sum(0), axis_name)
        c = agg.layer_coefficients(mask, p, bias_correct=bias_correct,
                                   counts=counts)
        return jax.tree.map(lambda g, i: agg._weight_leaf(g, i, c), grads,
                            ids)

    backends.aggregate_grads_local = local_only
from chipbench import run
res = run.main(["--workload", "tiny.m", "--seed", "9", "--seconds", "0.1",
                "--trace", "0"], allow_cpu=True,
               cell_files=(bench_file, data_dir))
print("RESULT " + json.dumps(res["correct"]))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["exchange", "exchange_left_out"])
def test_four_devices(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    arg = json.dumps([str(ROOT), str(CELL[0]), str(CELL[1]), broken])
    out = subprocess.run([sys.executable, "-c", CHILD, arg], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    (line,) = [x for x in out.stdout.splitlines() if x.startswith("RESULT")]
    assert json.loads(line.split(" ", 1)[1]) is (not broken), out.stderr[-2000:]
