"""The family modules: the dense layout pinned as it was written down
before it moved into ``families/dense.py``, and the shared code (weights,
the reference's fold and int8 wire, the change norms, the FLOP count)
driven through a family of another layout."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, flops, harness, inputs, reference
from conftest import DATA


def arch(name):
    path = (DATA / "configs" / "tiny.json" if name == "tiny"
            else harness.ROOT / "chipbench" / "configs" / f"{name}.json")
    return json.loads(path.read_text())["arch"]


SHAPES = {
    "tiny": {
        "embed": (512, 128),
        "blocks": {
            "norm1": (2, 128),
            "attn": {"wq": (2, 128, 128), "wk": (2, 128, 64),
                     "wv": (2, 128, 64), "wo": (2, 128, 128),
                     "bq": (2, 128), "bk": (2, 64), "bv": (2, 64)},
            "norm2": (2, 128),
            "mlp": {"wg": (2, 128, 256), "wu": (2, 128, 256),
                    "wd": (2, 256, 128)}},
        "final_norm": (128,), "lm_head": (128, 512)},
    "qwen1.5-4b": {
        "embed": (19456, 2560),
        "blocks": {
            "norm1": (4, 2560),
            "attn": {"wq": (4, 2560, 2560), "wk": (4, 2560, 2560),
                     "wv": (4, 2560, 2560), "wo": (4, 2560, 2560),
                     "bq": (4, 2560), "bk": (4, 2560), "bv": (4, 2560)},
            "norm2": (4, 2560),
            "mlp": {"wg": (4, 2560, 6912), "wu": (4, 2560, 6912),
                    "wd": (4, 6912, 2560)}},
        "final_norm": (2560,), "lm_head": (2560, 19456)},
    "chatglm3-6b": {
        "embed": (8192, 4096),
        "blocks": {
            "norm1": (2, 4096),
            "attn": {"wq": (2, 4096, 4096), "wk": (2, 4096, 256),
                     "wv": (2, 4096, 256), "wo": (2, 4096, 4096),
                     "bq": (2, 4096), "bk": (2, 256), "bv": (2, 256)},
            "norm2": (2, 4096),
            "mlp": {"wg": (2, 4096, 13696), "wu": (2, 4096, 13696),
                    "wd": (2, 13696, 4096)}},
        "final_norm": (4096,), "lm_head": (4096, 8192)},
}
# sha256 of the tiny configuration's seed-0 weights, leaf bytes in the
# tree's order, as inputs.make_weights made them before the move
TINY_SEED0 = "f4d92bcf98270d8e7647910e4718784da988a6abc313fb5896eb19e76942cb92"


@pytest.mark.parametrize("name", list(SHAPES))
def test_dense_weight_shapes(name):
    a = arch(name)
    assert families.load(a).weight_shapes(a) == SHAPES[name]
    made = jax.eval_shape(lambda: inputs.make_weights(a, 0))
    assert jax.tree.map(lambda x: x.shape, made) == SHAPES[name]


def test_tiny_weights_checksum():
    h = hashlib.sha256()
    for x in jax.tree.leaves(inputs.make_weights(arch("tiny"), 0)):
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == TINY_SEED0


# A family whose layer 0 is a subtree of its own beside a stack of layers
# 1..3, with uneven costs.
STUB = '''
import jax
import numpy as np


def weight_shapes(a):
    n = a["L"] - 1
    return {"embed": (5, 3), "first": {"w": (3, 3)},
            "stack": {"g": (n, 3), "w": (n, 3, 3)}, "head": (3, 5)}


def init_leaf(path, shape, key):
    return jax.random.normal(key, shape)


def logits(params, a, tok, mm):
    h = mm("bsd,de->bse", params["embed"][tok], params["first"]["w"])
    for l in range(a["L"] - 1):
        h = h + mm("bsd,de->bse", h * params["stack"]["g"][l],
                   params["stack"]["w"][l])
    return mm("bsd,dv->bsv", h, params["head"])


def layer_map(a):
    rest = tuple(range(1, a["L"]))
    return {"embed": 0, "first": {"w": 0},
            "stack": {"g": rest, "w": rest}, "head": a["L"] - 1}


def costs(a):
    return (np.array([10.0, 20.0, 30.0, 40.0]), np.array([1.0, 2.0, 3.0, 4.0]),
            7)
'''
LAYERS = {"embed": [0] * 5, "first/w": [0] * 3, "stack/g": [1, 2, 3],
          "stack/w": [1, 2, 3], "head": [3] * 3}


@pytest.fixture
def stub(tmp_path, monkeypatch):
    (tmp_path / "stub.py").write_text(STUB)
    monkeypatch.setattr(families, "DIR", tmp_path)
    return {"family": "stub", "L": 4}


def _by_name(tree):
    return {"/".join(k.key for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("wire", ["none", "int8"])
def test_fold_puts_each_layer_on_its_rows(stub, wire):
    w = inputs.make_weights(stub, 3)
    d = inputs.make_weights(stub, 4)
    acc = jax.tree.map(lambda x: x + 1.0, w)
    c_row = np.array([0.5, 2.0, 3.0, 5.0], np.float32)
    got = _by_name(reference.fold(acc, d, jnp.asarray(c_row),
                                  reference.row_layers(stub), wire))
    acc, d = _by_name(acc), _by_name(d)
    for name, lays in LAYERS.items():
        for r, lay in enumerate(lays):
            x = d[name][r]
            if wire == "int8":
                # the per-row wire form: one scale per layer row of a
                # stacked leaf, one per leaf otherwise
                whole = d[name] if name in ("embed", "first/w", "head") \
                    else x
                amax = np.abs(whole).max()
                x = np.rint(x * (127.0 / amax)) * (amax / 127.0)
            np.testing.assert_allclose(got[name][r],
                                       acc[name][r] + c_row[lay] * x,
                                       rtol=1e-6, atol=1e-6)


def test_change_norms_names_rows(stub):
    w0 = inputs.make_weights(stub, 3)
    w1 = inputs.make_weights(stub, 4)
    got = reference.change_norms(w1, w0, stub)
    w0, w1 = _by_name(w0), _by_name(w1)
    want = {}
    for name in ("embed", "first/w", "head"):
        want[name] = np.linalg.norm(w1[name] - w0[name])
    for name in ("stack/g", "stack/w"):
        for r in range(3):
            want[f"{name}/{r}"] = np.linalg.norm(w1[name][r] - w0[name][r])
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6)


def test_round_flops_hand_sum(stub):
    seq = 2
    # client 0: 3 real rows, keeps layers 0, 2 and 3; client 1: 9 rows
    # capped at s_max 4, keeps the output layer alone
    got = flops.round_flops(stub, seq, np.array([3, 9]),
                            np.array([[1, 0, 1, 1], [0, 0, 0, 1]]), s_max=4)
    fwd = 2 * (100 + 7) + 2 * seq * 10
    bwd0 = 4 * (10 + 30 + 40 + 7) + 4 * seq * (1 + 3 + 4)
    bwd1 = 4 * (40 + 7) + 4 * seq * 4
    assert got == 3 * seq * (fwd + bwd0) + 4 * seq * (fwd + bwd1)


def test_reference_round_on_stub(stub):
    """The reference's whole round runs on a layout that is not dense."""
    ref = reference.Reference(stub, wire="int8")
    xb = np.arange(2 * 2 * 4, dtype=np.int32).reshape(2, 2, 4) % 5
    w = ref.round(inputs.make_weights(stub, 3), xb, np.array([2, 1]),
                  np.array([[1, 1, 1, 1], [1, 0, 0, 1]]),
                  np.zeros(4, np.float32), 0.1, 2)
    norms = reference.change_norms(w, inputs.make_weights(stub, 3), stub)
    assert all(np.isfinite(v) and v > 0 for v in norms.values()), norms


def test_unknown_family_names_the_file():
    with pytest.raises(SystemExit, match=r"families/nope\.py"):
        inputs.make_weights({"family": "nope", "L": 2}, 0)
