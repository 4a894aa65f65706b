"""chip_smoke.py off the chip: it refuses a CPU, and its fold tolerance
separates f32 rounding from a fold done in bfloat16."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def _params(seed):
    rng = np.random.default_rng(seed)
    init = {"w": rng.normal(0, 0.02, (64, 256)).astype(np.float32),
            "g": np.ones((256,), np.float32)}
    update = {k: rng.normal(0, 1e-3, v.shape).astype(np.float32)
              for k, v in init.items()}
    return init, update


def test_fold_tolerance(chip_smoke):
    init, upd = _params(0)
    ref = {k: init[k] - upd[k] for k in init}
    # the same update rounded once more in f32 passes
    near = {k: init[k] - (upd[k] * np.float32(1 + 2 ** -23)) for k in init}
    res = chip_smoke.compare("f32", near, ref, init)
    assert res["max_abs_diff"] > 0
    # an update rounded to bfloat16 fails
    bf16 = {k: init[k] - np.asarray(jnp.asarray(upd[k], jnp.bfloat16),
                                    np.float32) for k in init}
    with pytest.raises(SystemExit, match="tolerance"):
        chip_smoke.compare("bf16", bf16, ref, init)


def test_int8_tolerance_allows_rare_rounding_flips(chip_smoke):
    init, upd = _params(1)
    ref = {k: init[k] - upd[k] for k in init}
    step = float(np.abs(upd["w"]).max()) / 127     # one int8 step
    flipped = {k: v.copy() for k, v in ref.items()}
    flipped["w"][0, :3] += step
    chip_smoke.compare("one flip", flipped, ref, init, int8=True)
    flipped["w"][:, :] += step * (np.arange(256) % 2)   # half the entries
    with pytest.raises(SystemExit, match="of entries differ"):
        chip_smoke.compare("many flips", flipped, ref, init, int8=True)
