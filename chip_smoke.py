#!/usr/bin/env python3
"""Run the ADEL-FL round on a TPU through ``run_training`` and check it.

    python chip_smoke.py              # one chip: the Qwen1.5-4B chip share
    python chip_smoke.py --chips 4    # the shard_map round over four chips

One chip, in order:

1. fail unless JAX's first device is a TPU; print its kind and count;
2. compile the ``temporal`` round step at the run's shapes and print its
   compiled peak;
3. train ``ROUNDS`` rounds of the ``qwen1.5-4b`` chip share
   (:mod:`repro.configs.qwen1_5_4b`: published widths, 4 layers, 1/8 of
   the vocabulary) on ``temporal`` with the ``jnp`` Eq. 5 fold; print
   s/round per round, the first and last token loss (finite) and the
   ``History``;
4. run one round with the Pallas fold (``adel_agg``) and one with the int8
   wire and its Pallas fold (``adel_agg_q8``); each compiled step must hold
   a ``tpu_custom_call``, and the updated parameters must match the same
   round with the ``jnp`` fold (tolerances below).

Four chips (``--chips 4``), and nothing else: one ``shard_map`` round with
one client per chip over ``make_client_mesh()``, checked to spread the
client axis over all four devices, against the same round on ``temporal``
on one device.

The last line of standard output is ``{"ok": true, "device": {...}}``; any
failed check exits non-zero before it is printed. One process drives the
chip(s); nothing here starts another.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ARCH = "qwen1.5-4b"
REDUCED = False     # the registered config: one v5e chip's share at full width
SEQ = 512           # tokens per training row
U = 8               # clients per round on one chip
S_MAX = 8           # rows per client; the activations of 32 would not fit
ROUNDS = 3
SEED = 0

# Tolerances on the updated parameters w_1, per entry: a fraction of the
# largest entry of the reference round's update |w_1 - w_0|, plus two f32
# ulps of the entry itself (w_1 = w_0 - update is rounded to f32, so an
# update that differs in its last bit can move w_1 by one ulp).
# * f32 folds. The Pallas and jnp folds form the same f32 products and sum
#   clients in the same scan order, so they differ only where the two
#   compiled programs round differently: far below 2**-12 of the update. A
#   fold that rounded the update to bfloat16 (relative error 2**-9) fails
#   by 8x.
FOLD_TOL = 2.0 ** -12
# * int8 folds. Both dequantize the same wire format, but a last-bit
#   difference in a client's f32 delta between the two programs can move an
#   element across a rounding boundary: one int8 step, amax / 127 of that
#   client's layer. So at most INT8_FLIP_SHARE of the entries may exceed
#   the f32 tolerance, and none may exceed two such steps of the update.
INT8_STEP_TOL = 2.0 / 127
INT8_FLIP_SHARE = 1e-3


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def round_args(model, U: int, L: int):
    """Shapes of one round step's arguments, as RoundRuntime passes them."""
    import jax
    import jax.numpy as jnp
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(model.init, sds((2,), np.uint32))
    return (params, sds((U, S_MAX, SEQ + 1), jnp.int32),
            sds((U, S_MAX), jnp.int32), sds((U, S_MAX), jnp.float32),
            sds((U, L), jnp.float32), sds((L,), jnp.float32),
            sds((), jnp.float32), None)


def compile_step(spec, cfg, U: int):
    """Compile the round step ``spec`` selects at the run's shapes."""
    from repro.fl.backends import make_backend
    from repro.fl.tasks import make_lm_model
    model = make_lm_model(cfg)
    step = make_backend(spec, model)._step(True, False)    # adel: Eq. 5
    return step.lower(*round_args(model, U, cfg.L)).compile()


def peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def train(spec, U: int, rounds: int, **kw):
    from repro.launch.train import run_training
    return run_training(ARCH, reduced=REDUCED, exec=spec, U=U, seq=SEQ,
                        s_max_cap=S_MAX, rounds=rounds, seed=SEED, **kw)


def init_params(cfg):
    """The run's initial parameters: ``RoundRuntime.run`` draws them from
    the second half of ``split(PRNGKey(seed))``."""
    import jax
    from repro.fl.tasks import make_lm_model
    k_init = jax.random.split(jax.random.PRNGKey(SEED))[1]
    return jax.device_get(make_lm_model(cfg).init(k_init))


def compare(name: str, got, ref, init, *, int8: bool = False) -> dict:
    """Largest difference of ``got`` from ``ref`` (both updated params),
    judged against the largest entry of ref's update from ``init``."""
    import jax
    got, ref = jax.tree.leaves(jax.device_get(got)), jax.tree.leaves(ref)
    init = jax.tree.leaves(init)
    f64 = lambda a: np.asarray(a, np.float64)
    u_max = max(float(np.abs(f64(r) - i).max()) for r, i in zip(ref, init))
    p_max = max(float(np.abs(i).max()) for i in init)
    tol = INT8_STEP_TOL if int8 else FOLD_TOL
    d_max = excess = 0.0
    n_over = n = 0
    for g, r in zip(got, ref):
        d = np.abs(f64(g) - f64(r))
        ulps = 2.0 * np.spacing(np.abs(np.asarray(r, np.float32)))
        d_max = max(d_max, float(d.max()))
        n_over += int((d > FOLD_TOL * u_max + ulps).sum())
        excess = max(excess, float((d - ulps).max()) / u_max)
        n += d.size
    res = {"max_abs_diff": d_max, "max_abs_update": u_max,
           "max_diff_beyond_2ulp_over_update": excess,
           "share_over_f32_tol": n_over / n}
    print(f"[{name}] {json.dumps(res)}", flush=True)
    check(0.0 < u_max < p_max, f"{name}: the round's update {u_max} is not "
          f"a small step from the initial parameters (max {p_max})")
    check(excess <= tol, f"{name}: differs by {excess} of the update "
          f"beyond 2 ulps, over the tolerance {tol}")
    if int8:
        check(res["share_over_f32_tol"] <= INT8_FLIP_SHARE,
              f"{name}: {res['share_over_f32_tol']} of entries differ by "
              f"more than {FOLD_TOL} of the update")
    return res


def one_chip(cfg) -> None:
    import jax
    from repro import obs
    from repro.fl.spec import ExecSpec

    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    jnp_spec = ExecSpec(backend="temporal")
    peak = peak_bytes(compile_step(jnp_spec, cfg, U))
    print(f"[peak] temporal round step, U={U} s_max={S_MAX} seq={SEQ}: "
          f"compiled peak {peak} B of {limit} B device memory", flush=True)
    check(limit is None or peak < limit, "the round step does not fit")

    tracer = obs.Tracer()
    _, hist = train(jnp_spec, U, ROUNDS, tracer=tracer, verbose=True)
    ledger = hist.telemetry["ledger"]
    check(len(ledger) == ROUNDS, f"ran {len(ledger)} of {ROUNDS} rounds")
    for row in ledger:
        check(row["batch_padded"] == U * S_MAX,
              f"round {row['round']} ran at another s_max than compiled")
        print(f"[round {row['round']}] {row['wall_round_s']} s/round"
              + (" (includes compile)" if row["round"] == 1 else ""))
    loss = [float(x) for x in hist.train_loss]
    check(all(np.isfinite(loss)), f"non-finite token loss {loss}")
    print(f"[loss] first {loss[0]} last {loss[-1]}")
    hist_d = {k: v for k, v in hist.as_dict().items() if k != "telemetry"}
    print(f"[history] {json.dumps(hist_d)}", flush=True)

    init = init_params(cfg)
    for comp, kernel in (("none", "adel_agg"), ("int8", "adel_agg_q8")):
        ref, _ = train(ExecSpec(backend="temporal", compression=comp), U, 1,
                       verbose=False)
        ref = jax.device_get(ref)
        spec = ExecSpec(backend="temporal", compression=comp,
                        agg_impl="pallas")
        text = compile_step(spec, cfg, U).as_text()
        check("tpu_custom_call" in text,
              f"the Pallas-fold step ({kernel}) holds no tpu_custom_call")
        got, _ = train(spec, U, 1, verbose=False)
        compare(f"{kernel} vs jnp fold, compression={comp}", got, ref, init,
                int8=comp == "int8")


def four_chips(cfg) -> None:
    import jax
    from repro.fl.spec import ExecSpec
    from repro.launch.mesh import make_client_mesh

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, JAX has {len(devs)}")
    U4 = 4                                  # one client per chip
    spec = ExecSpec(backend="shard_map", mesh=make_client_mesh())
    # lowered from unplaced shapes, as the runtime hands over its arrays:
    # the placement below is the one the shard_map step itself imposes
    compiled = compile_step(spec, cfg, U4)
    print(f"[peak] shard_map round step, U={U4} s_max={S_MAX} seq={SEQ}: "
          f"compiled peak {peak_bytes(compiled)} B per device", flush=True)
    check("all-reduce" in compiled.as_text(), "no all-reduce in the step")
    xb = compiled.input_shardings[0][1]
    check(len(xb.device_set) == 4
          and xb.shard_shape((U4, S_MAX, SEQ + 1))[0] == 1,
          f"client batches are not split over 4 devices: {xb}")

    got, _ = train(spec, U4, 1, verbose=True)
    held = {d.id for leaf in jax.tree.leaves(got)
            for d in leaf.sharding.device_set}
    check(len(held) == 4, f"updated params live on devices {held} only")
    n_param = sum(leaf.nbytes for leaf in jax.tree.leaves(got))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    print(f"[devices] peak bytes in use {peaks}; params {n_param} B")
    check(all(p >= n_param for p in peaks),
          "a device never held the round's parameters")
    got = jax.device_get(got)
    ref, _ = train(ExecSpec(backend="temporal"), U4, 1, verbose=False)
    compare("shard_map over 4 chips vs temporal on one", got, ref,
            init_params(cfg))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map round over four chips "
                         "and its one-device reference")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"no TPU: JAX's first device is "
          f"{dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[device] {device}", flush=True)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    print(f"[cache] {use_compile_cache()}")
    cfg = get_config(ARCH)
    cfg = cfg.reduced() if REDUCED else cfg
    (four_chips if args.chips == 4 else one_chip)(cfg)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
