"""The reference against the program at a small size, and the check that
decides ``correct`` against a broken round step and the control.

A whole run (``run.main``) of the small cell in ``data/``, on the CPU with
the look for a chip skipped. The cell computes in float32, so the program
matches the float32 reference to rounding; each fault planted under the
round step must make ``correct`` false, and the float8 control must read
above a limit.
"""
import jax.numpy as jnp
import pytest

from chipbench import calibrate, harness, run
from conftest import CELL

ARGS = ["--trace", "0"]


def _run(seed, seconds=1.0, cell="tiny.t"):
    return run.main(ARGS + ["--workload", cell, "--seed", str(seed),
                            "--seconds", str(seconds)],
                    allow_cpu=True, cell_files=CELL)


def test_program_matches_reference():
    res = _run(2 ** 31 + 7)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 3
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"round_s", "client_tokens_per_s",
                                   "setup_s"}


def test_int8_wire_matches_reference():
    """The int8 wire and its Pallas fold (interpreted on the CPU) against
    the reference's own quantization."""
    res = _run(5, cell="tiny.q")
    assert res["correct"], res["compared"]


def _unchanged(run_round):
    return lambda self, params, *a, **k: params


def _half_batch(run_round):
    def broken(self, params, xb, yb, wb, mask, p, eta, **kw):
        S = (wb > 0).sum(1, keepdims=True)
        keep = jnp.arange(wb.shape[1])[None, :] < jnp.maximum(S // 2, 1)
        wb = jnp.where(keep, 1.0 / jnp.maximum(S // 2, 1), 0.0)
        return run_round(self, params, xb, yb, wb, mask, p, eta, **kw)
    return broken


def _token_altered(run_round):
    def broken(self, params, xb, yb, wb, mask, p, eta, **kw):
        xb = xb.at[:, :, 5].set((xb[:, :, 5] + 1) % 500)
        return run_round(self, params, xb, yb, wb, mask, p, eta, **kw)
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_faults_fail(monkeypatch, fault):
    from repro.fl.backends import TemporalBackend
    monkeypatch.setattr(TemporalBackend, "run_round",
                        fault(TemporalBackend.run_round))
    # a short window: a step that does nothing would use up the plan
    res = _run(11, seconds=0.05)
    assert not res["correct"], res["compared"]


def test_control_fails():
    rows = calibrate.main(["--workload", "tiny.t", "--seeds", "",
                           "--control-seeds", "3"], allow_cpu=True,
                          cell_files=CELL)
    (row,) = rows
    assert row["kind"] == "control"
    limits = harness.load_cell("tiny.t", *CELL).limits
    assert any(row[k] > limits[k] for k in limits), row
