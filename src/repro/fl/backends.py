"""Pluggable execution backends for the unified round runtime.

:class:`repro.fl.runtime.RoundRuntime` plans a round (policy, padding,
clock, eval) and hands the padded fixed-shape round inputs to an
:class:`ExecutionBackend`, which owns HOW the cohort's client updates are
computed and aggregated:

* :class:`DenseBackend`     — one vmap over the whole cohort; aggregation is
  :func:`repro.core.aggregation.aggregate_grads` (the original
  ``run_federated`` path).
* :class:`ChunkedBackend`   — the cohort axis is processed ``chunk_size``
  clients at a time; per-chunk partial aggregates from
  :func:`repro.core.aggregation.aggregate_grads_chunk` are summed on the
  host — a software psum that never materializes a full ``(cohort, ...)``
  delta pytree (the original fleet-engine path).
* :class:`ShardMapBackend`  — the chunk loop becomes a REAL client mesh
  axis: ``jax.shard_map`` over :func:`repro.launch.mesh.batch_axes` with
  :func:`repro.core.aggregation.aggregate_grads_local` (``jax.lax.psum``).
  Testable on a CPU host via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
* :class:`TemporalBackend`  — clients are grad-accumulation microbatches:
  ``jax.lax.scan`` over the cohort axis with the Eq. 5 coefficient fold of
  :func:`repro.core.aggregation.weight_by_layer` (the big-arch LM layout
  from ``launch.steps.make_train_step``), so peak memory is ONE delta
  pytree regardless of cohort size. Required for 480B-class architectures.
* :class:`BufferedBackend`  — the semi-async (delayed-gradient) variant of
  dense: layers a straggler did NOT finish by the deadline are banked in a
  server-side carry buffer and folded into a later round's update with
  staleness weight ``lam ** tau`` (see the class docstring). ``lam=0``
  delegates every round to the dense step — trajectory-bit-identical.
* :class:`HierarchicalBackend` — two-tier edge aggregation: the cohort
  partitions into edge regions (region ids from the round context's
  population draw, or a contiguous fallback split), each region computes
  its partial aggregate via the chunk machinery
  (:func:`repro.core.aggregation.aggregate_grads_chunk` /
  ``hetero_overlap_partials`` against GLOBAL counts; int8 wire payloads
  stay compressed region-local), and one global Eq. 5 fold applies the
  summed partials. A single region delegates to the dense step bit-exactly.

All of them produce the same updates up to float summation order, which
``tests/test_backends.py`` asserts end-to-end. Each backend keeps its own
jit cache keyed by ``(bias_correct, hetero)``, so retracing happens at most
once per aggregation rule; HeteroFL width-overlap aggregation
(:func:`repro.core.aggregation.hetero_overlap_partials`) flows through the
same chunk/psum/scan machinery as the layer-wise rule.

Every backend DONATES the incoming ``params`` buffers to its round step
(``jax.jit(..., donate_argnums=0)``): the server update aliases the old
weights in place, halving peak parameter memory on large models. The
runtime's round loop never reads a params buffer after handing it to
``run_round`` — callers that do must construct the backend with
``donate=False``. The chunked backend only donates in its final apply step
(every chunk partial reads the same params).

Backends are selected through :class:`repro.fl.spec.ExecSpec`
(``make_backend(exec=spec, model)``) or by legacy name: ``make_backend(
"dense" | "chunked" | "shard_map" | "temporal" | "buffered", model, ...)``
— both resolve through :meth:`ExecSpec.resolve`, so trajectories are
bit-identical either way.

Compression: every backend accepts a ``compression=`` spec
(:mod:`repro.core.compression` — ``"int8"`` symmetric quantization or
``"topk8"`` sparsification). The compressed payload is what the reduction
CONSUMES: dense/temporal fold it through
:func:`repro.core.compression.aggregate_compressed` (optionally the fused
Pallas ``adel_agg_q8`` kernel via ``agg_impl="pallas"``), chunked's
chunk-sum accumulates partials computed from int8 chunk payloads, and
shard_map quantizes inside the shard-local function so each shard's
reduction reads int8 (the psum itself combines float32 partials).
``agg_impl="pallas"`` also routes UNcompressed dense/temporal aggregation
through ``kernels.ops.adel_aggregate_pallas`` (interpret mode on CPU).
HeteroFL width-overlap rounds are entry-wise means over width masks — not
an Eq. 5 coefficient fold — and reject compression with a ``ValueError``.

Telemetry: every backend carries the runtime's tracer (``set_tracer``,
default :data:`repro.obs.NULL_TRACER`). The fused single-dispatch backends
(dense / shard_map / temporal, and buffered's train + on-time fold) emit
one ``round_step`` span (``fused=True``) per round plus
``aggregate_bytes_logical`` / ``aggregate_bytes_wire`` counters (dense
float32 pytree size vs post-compression payload size, both analytic and
exactly deterministic); the chunked backend emits one ``local_train`` span
and one counter pair per chunk and a separate ``aggregate`` span around the
final apply. Every span is also a ``repro.<phase>`` profiler annotation,
tracer or not. Tracers with ``sync=True`` block on step results so spans
measure device work rather than async dispatch — numerics are untouched
either way.

Inside the fused round steps, ``jax.named_scope`` s mark what each device
op belongs to: ``local_train`` (each client's local update, forward and
backward; backward ops carry ``transpose(jvp(...))`` below it), ``fold``
(the Eq. 5 coefficient weighting and the accumulator add, wire
quantization included), ``exchange`` (the shard_map step's psums) and
``server_apply`` (``w - agg``). Scopes are op metadata only: the compiled
program is the same without them.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

import numpy as np

from repro import obs
from repro.core.aggregation import (aggregate_grads, aggregate_grads_chunk,
                                    aggregate_grads_local,
                                    aggregate_with_coeffs,
                                    hetero_overlap_mean,
                                    hetero_overlap_partials,
                                    layer_coefficients, weight_by_layer)
from repro.core.compression import (aggregate_compressed, compress_deltas,
                                    make_compression, payload_bytes)
from repro.core.straggler import late_arrival_delays, late_p_layers
from repro.fl.client import batched_client_deltas, local_update
# the canonical name tuples live next to ExecSpec (re-exported here for
# back-compat: `from repro.fl.backends import BACKENDS` keeps working)
from repro.fl.spec import AGG_IMPLS, BACKENDS, ExecSpec

from jax.sharding import PartitionSpec as P

__all__ = ["BACKENDS", "AGG_IMPLS", "ExecSpec", "ExecutionBackend",
           "DenseBackend", "ChunkedBackend", "ShardMapBackend",
           "TemporalBackend", "BufferedBackend", "HierarchicalBackend",
           "make_backend"]

PyTree = Any


def _sub32(w: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """dtype-preserving server update for float32 aggregates."""
    return (w.astype(jnp.float32) - d.astype(jnp.float32)).astype(w.dtype)


class ExecutionBackend:
    """Executes one federated round over a padded fixed-shape cohort.

    ``run_round`` receives per-client batches ``xb/yb/wb`` with leading axis
    ``U_pad = cohort_pad(cohort_size)``, the (U_pad, L) contribution mask
    (padded rows all-zero, so they contribute nothing), the (L,)
    zero-contributor probabilities ``p``, the round's learning rate, and —
    for HeteroFL rounds — a width-mask pytree with leading axis U_pad.
    It returns the updated global params.

    With ``donate=True`` (default) the round step donates the ``params``
    argument: the input buffers are invalidated once the step runs, so the
    caller must treat ``run_round`` as consuming its params.
    """

    name = "base"
    #: backends that carry state across rounds (the buffered backend) need
    #: the runtime's per-round :class:`repro.fl.runtime.RoundContext`
    #: (simulated clock + straggler-model rates) passed as ``ctx=``
    needs_ctx = False

    def __init__(self, model, *, local_iters: int = 1, l2: float = 0.0,
                 donate: bool = True, compression=None,
                 agg_impl: str = "jnp"):
        self.model = model
        self.local_iters = int(local_iters)
        self.l2 = float(l2)
        self.donate = bool(donate)
        self.compression = make_compression(compression)
        self.agg_impl = str(agg_impl)
        assert self.agg_impl in AGG_IMPLS, \
            f"unknown agg_impl {agg_impl!r}; known: {AGG_IMPLS}"
        self.tracer = obs.NULL_TRACER
        self._bytes_cache: dict[int, tuple[int, int]] = {}

    def set_tracer(self, tracer) -> None:
        """Attach the runtime's tracer (:class:`repro.obs.Tracer`) so the
        backend's ``round_step`` / ``local_train`` / ``aggregate`` spans and
        bytes counters land in the same event stream."""
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER

    def _round_bytes(self, params_like: PyTree, U: int) -> tuple[int, int]:
        """Analytic (logical, wire) payload bytes for a U-client reduction
        over this backend's compression config — deterministic, so the
        benchmark gate can match them exactly. ``params_like`` supplies
        leaf shapes only (the round's output params work)."""
        key = int(U)
        if key not in self._bytes_cache:
            ids = self.model.layer_ids(params_like)
            self._bytes_cache[key] = payload_bytes(params_like, ids, key,
                                                   self.compression)
        return self._bytes_cache[key]

    def _count_bytes(self, params_like: PyTree, U: int) -> None:
        logical, wire = self._round_bytes(params_like, U)
        self.tracer.count("aggregate_bytes_logical", logical,
                          backend=self.name)
        self.tracer.count("aggregate_bytes_wire", wire, backend=self.name)

    def _check_rule(self, wmasks) -> None:
        """HeteroFL's width-overlap mean is an entry-wise mean, not an
        Eq. 5 coefficient fold — the quantized wire format has no sound
        dequant-weight for it."""
        if wmasks is not None and self.compression.mode != "none":
            raise ValueError(
                f"compression={self.compression.mode!r} is incompatible "
                f"with HeteroFL width-mask aggregation")

    def _traced_fused(self, step, params, *args):
        """Run a fused train+aggregate jit step under a ``round_step``
        span (the single-dispatch backends cannot split aggregation out of
        the compiled step on the host; the step's named scopes split it on
        the device). A tracer with ``sync=True`` blocks on the result so
        the span measures device work, not async dispatch; trajectories
        are unchanged."""
        tracer = self.tracer
        with tracer.span("round_step", backend=self.name, fused=True):
            out = step(params, *args)
            if tracer.sync:
                jax.block_until_ready(out)
        if tracer.active:
            self._count_bytes(out, int(args[3].shape[0]))   # args[3] = mask
        return out

    @property
    def _donate_params(self) -> tuple:
        """donate_argnums for round steps whose argument 0 is params."""
        return (0,) if self.donate else ()

    def cohort_pad(self, U: int) -> int:
        """Smallest padded cohort width >= U this backend can execute."""
        return int(U)

    def reset_state(self) -> None:
        """Clear any cross-round server-side state (carry buffers). The
        runtime calls this at the start of every ``run`` so one backend
        instance can drive several independent trainings. Stateless
        backends are a no-op."""

    def warm_up(self, params: PyTree, xb, yb, wb, mask, p, eta, *,
                bias_correct: bool = True, wmasks: PyTree | None = None,
                ctx=None) -> float:
        """AOT warm-up: trace + compile + execute the round step once for
        the exact argument shapes/dtypes, leaving ``params`` and all
        cross-round state untouched. Returns seconds spent.

        ``jit.lower(...).compile()`` populates XLA's executable cache but
        NOT jax's jit dispatch cache — the first real call would still pay
        the full dispatch-path setup — so the warm-up EXECUTES the real
        ``run_round`` on a private zero-filled copy of ``params``
        (donation-safe) with the caller's round arrays, discards the
        result, and calls :meth:`reset_state` to erase anything the dummy
        round banked (the buffered carry slots, the hierarchical region
        census). Host-side branch decisions (buffered's bank-or-not,
        hierarchical's region split) read the real ``mask``/``ctx``
        values, so the variant round 0 will run is the variant that gets
        compiled. Telemetry is suppressed for the dummy round (its
        profiler annotations remain, inside the runtime's ``warm_up``).
        """
        t0 = obs.now()
        dummy = jax.tree.map(lambda a: jnp.zeros(jnp.shape(a),
                                                 jnp.result_type(a)), params)
        tracer = self.tracer
        self.tracer = obs.NULL_TRACER
        try:
            out = self.run_round(dummy, xb, yb, wb, mask, p, eta,
                                 bias_correct=bias_correct, wmasks=wmasks,
                                 ctx=ctx)
            jax.block_until_ready(out)
        finally:
            self.tracer = tracer
            self.reset_state()
        return obs.now() - t0

    def run_round(self, params: PyTree, xb, yb, wb, mask, p, eta, *,
                  bias_correct: bool, wmasks: PyTree | None = None,
                  ctx=None) -> PyTree:
        raise NotImplementedError

    def describe(self) -> dict:
        return {"backend": self.name, "donate": self.donate,
                "compression": self.compression.mode,
                "agg_impl": self.agg_impl}

    # shared sub-computations -------------------------------------------
    def _deltas(self, params, xb, yb, wb, eta):
        with jax.named_scope("local_train"):
            return batched_client_deltas(self.model.loss, params, xb, yb, wb,
                                         eta, local_iters=self.local_iters,
                                         l2=self.l2)


class DenseBackend(ExecutionBackend):
    """Whole cohort in one vmap + one monolithic aggregation."""

    name = "dense"

    def __init__(self, model, *, local_iters: int = 1, l2: float = 0.0,
                 donate: bool = True, compression=None,
                 agg_impl: str = "jnp"):
        super().__init__(model, local_iters=local_iters, l2=l2, donate=donate,
                         compression=compression, agg_impl=agg_impl)
        self._steps: dict[tuple, Callable] = {}

    def _step(self, bias_correct: bool, hetero: bool) -> Callable:
        key = (bias_correct, hetero)
        if key not in self._steps:
            comp = self.compression

            def step(params, xb, yb, wb, mask, p, eta, wmasks):
                deltas = self._deltas(params, xb, yb, wb, eta)
                ids = self.model.layer_ids(params)
                with jax.named_scope("fold"):
                    if hetero:
                        num, den = hetero_overlap_partials(deltas, wmasks,
                                                           mask[:, 0])
                        agg = hetero_overlap_mean(num, den)
                    elif comp.mode != "none":
                        # the reduction consumes the int8 wire payload: the
                        # float32 delta tree never feeds the aggregation
                        payload = compress_deltas(deltas, ids, comp)
                        agg = aggregate_compressed(
                            payload, params, ids, mask, p, cfg=comp,
                            bias_correct=bias_correct,
                            agg_impl=self.agg_impl)
                    elif self.agg_impl == "pallas":
                        from repro.kernels.ops import adel_aggregate_pallas
                        agg = adel_aggregate_pallas(deltas, ids, mask, p,
                                                    bias_correct=bias_correct)
                    else:
                        agg = aggregate_grads(deltas, ids, mask, p,
                                              bias_correct=bias_correct)
                with jax.named_scope("server_apply"):
                    sub = (_sub32 if comp.mode != "none"
                           else lambda w, d: w - d)
                    return jax.tree.map(sub, params, agg)

            self._steps[key] = jax.jit(step,
                                       donate_argnums=self._donate_params)
        return self._steps[key]

    def run_round(self, params, xb, yb, wb, mask, p, eta, *,
                  bias_correct, wmasks=None, ctx=None):
        self._check_rule(wmasks)
        step = self._step(bool(bias_correct), wmasks is not None)
        return self._traced_fused(step, params, xb, yb, wb, mask, p, eta,
                                  wmasks)


class ChunkedBackend(ExecutionBackend):
    """Sequential software psum over a client-shard axis.

    The cohort is padded to a ``chunk_size`` multiple; each chunk's partial
    aggregate uses the GLOBAL per-layer contributor counts, so summing the
    partials over chunks equals the dense aggregation on the concatenated
    client axis. A single-chunk cohort falls through to the dense step.

    Every chunk partial reads the same ``params``, so only the final apply
    step (``params - agg``) donates the params buffers.
    """

    name = "chunked"

    def __init__(self, model, *, chunk_size: int = 16, local_iters: int = 1,
                 l2: float = 0.0, donate: bool = True, compression=None,
                 agg_impl: str = "jnp"):
        super().__init__(model, local_iters=local_iters, l2=l2, donate=donate,
                         compression=compression, agg_impl=agg_impl)
        self.chunk_size = max(int(chunk_size), 1)
        self._dense = DenseBackend(model, local_iters=local_iters, l2=l2,
                                   donate=donate, compression=compression,
                                   agg_impl=agg_impl)
        self._chunks: dict[tuple, Callable] = {}
        self._folds: dict[bool, Callable] = {}
        self._payload_step = None
        self._apply = jax.jit(
            lambda params, agg: jax.tree.map(lambda w, d: w - d, params, agg),
            donate_argnums=self._donate_params)
        self._apply32 = jax.jit(
            lambda params, agg: jax.tree.map(_sub32, params, agg),
            donate_argnums=self._donate_params)
        self._apply_hetero = jax.jit(
            lambda params, num, den: jax.tree.map(
                lambda w, d: w - d, params, hetero_overlap_mean(num, den)),
            donate_argnums=self._donate_params)

    def cohort_pad(self, U: int) -> int:
        c = min(self.chunk_size, int(U))   # never vmap dead padding
        return -(-int(U) // c) * c

    def set_tracer(self, tracer) -> None:
        super().set_tracer(tracer)
        self._dense.set_tracer(tracer)     # single-chunk fall-through

    def _chunk_step(self, bias_correct: bool, hetero: bool) -> Callable:
        key = (bias_correct, hetero)
        if key not in self._chunks:
            # NEVER donate params here: the same buffers feed every chunk
            @jax.jit
            def chunk_partial(params, xb, yb, wb, mask_c, p, eta, counts,
                              wmasks_c):
                deltas = self._deltas(params, xb, yb, wb, eta)
                ids = self.model.layer_ids(params)
                if hetero:
                    return hetero_overlap_partials(deltas, wmasks_c,
                                                   mask_c[:, 0])
                return aggregate_grads_chunk(deltas, ids, mask_c, p, counts,
                                             bias_correct=bias_correct)

            self._chunks[key] = chunk_partial
        return self._chunks[key]

    def _payload(self) -> Callable:
        """jit step producing one chunk's compressed wire payload — the
        int8 tuples are what crosses the jit boundary and what the
        chunk-sum consumes."""
        if self._payload_step is None:
            comp = self.compression

            # NEVER donate params here: the same buffers feed every chunk
            @jax.jit
            def chunk_payload(params, xb, yb, wb, eta):
                deltas = self._deltas(params, xb, yb, wb, eta)
                ids = self.model.layer_ids(params)
                return compress_deltas(deltas, ids, comp)

            self._payload_step = chunk_payload
        return self._payload_step

    def _fold(self, bias_correct: bool) -> Callable:
        """jit fold: dequantize + Eq. 5 weight one chunk payload (against
        GLOBAL counts) and accumulate into the float32 running aggregate.
        The accumulator is donated — the fold updates it in place."""
        if bias_correct not in self._folds:
            comp = self.compression

            def fold(acc, params, payload, mask_c, p, counts):
                ids = self.model.layer_ids(params)
                part = aggregate_compressed(
                    payload, params, ids, mask_c, p, cfg=comp, counts=counts,
                    bias_correct=bias_correct, agg_impl=self.agg_impl)
                return jax.tree.map(jnp.add, acc, part)

            self._folds[bias_correct] = jax.jit(fold, donate_argnums=(0,))
        return self._folds[bias_correct]

    def _run_round_compressed(self, params, xb, yb, wb, mask, p, eta, *,
                              bias_correct, U, c):
        payload_step = self._payload()
        fold = self._fold(bool(bias_correct))
        counts = mask.sum(0)                   # (L,) global contributors
        tracer = self.tracer
        acc = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), params)
        for c0 in range(0, U, c):
            sl = slice(c0, c0 + c)
            with tracer.span("local_train", backend=self.name,
                             chunk=c0 // c):
                payload = payload_step(params, xb[sl], yb[sl], wb[sl], eta)
                if tracer.sync:
                    jax.block_until_ready(payload)
            if tracer.active:
                self._count_bytes(params, c)
            acc = fold(acc, params, payload, mask[sl], p, counts)
        with tracer.span("aggregate", backend=self.name, chunks=-(-U // c)):
            out = self._apply32(params, acc)
            if tracer.sync:
                jax.block_until_ready(out)
        return out

    def run_round(self, params, xb, yb, wb, mask, p, eta, *,
                  bias_correct, wmasks=None, ctx=None):
        self._check_rule(wmasks)
        U = int(mask.shape[0])
        c = min(self.chunk_size, U)
        if U <= c:
            return self._dense.run_round(params, xb, yb, wb, mask, p, eta,
                                         bias_correct=bias_correct,
                                         wmasks=wmasks)
        if self.compression.mode != "none":
            return self._run_round_compressed(params, xb, yb, wb, mask, p,
                                              eta, bias_correct=bias_correct,
                                              U=U, c=c)
        hetero = wmasks is not None
        step = self._chunk_step(bool(bias_correct), hetero)
        counts = mask.sum(0)                       # (L,) global contributors
        tracer = self.tracer
        num = den = agg = None
        for c0 in range(0, U, c):
            sl = slice(c0, c0 + c)
            wm_c = (None if not hetero
                    else jax.tree.map(lambda m: m[sl], wmasks))
            with tracer.span("local_train", backend=self.name,
                             chunk=c0 // c):
                part = step(params, xb[sl], yb[sl], wb[sl], mask[sl], p, eta,
                            counts, wm_c)
                if tracer.sync:
                    jax.block_until_ready(part)
            if tracer.active:
                self._count_bytes(params, c)
            if hetero:
                n_p, d_p = part
                num = n_p if num is None else jax.tree.map(jnp.add, num, n_p)
                den = d_p if den is None else jax.tree.map(jnp.add, den, d_p)
            else:
                agg = part if agg is None else jax.tree.map(jnp.add, agg, part)
        with tracer.span("aggregate", backend=self.name,
                         chunks=-(-U // c)):
            out = (self._apply_hetero(params, num, den) if hetero
                   else self._apply(params, agg))
            if tracer.sync:
                jax.block_until_ready(out)
        return out

    def describe(self):
        return {**super().describe(), "chunk_size": self.chunk_size}


class ShardMapBackend(ExecutionBackend):
    """The chunk axis as a real client mesh axis: shard_map + lax.psum.

    The cohort is padded to a multiple of the mesh's batch shards; every
    shard computes its clients' deltas and local partials, and
    ``jax.lax.psum`` over :func:`repro.launch.mesh.batch_axes` combines
    counts and weighted sums — the hardware form of the chunk loop.
    """

    name = "shard_map"

    def __init__(self, model, *, mesh=None, local_iters: int = 1,
                 l2: float = 0.0, donate: bool = True, compression=None,
                 agg_impl: str = "jnp"):
        super().__init__(model, local_iters=local_iters, l2=l2, donate=donate,
                         compression=compression, agg_impl=agg_impl)
        self._mesh = mesh
        self._steps: dict[tuple, Callable] = {}

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_client_mesh
            self._mesh = make_client_mesh()
        return self._mesh

    @property
    def client_axes(self) -> tuple:
        from repro.launch.mesh import batch_axes
        return batch_axes(self.mesh)

    @property
    def n_shards(self) -> int:
        from repro.launch.mesh import batch_shards
        return batch_shards(self.mesh)

    def cohort_pad(self, U: int) -> int:
        n = self.n_shards
        return -(-int(U) // n) * n

    def _step(self, bias_correct: bool, hetero: bool) -> Callable:
        key = (bias_correct, hetero)
        if key not in self._steps:
            mesh = self.mesh
            ax = tuple(self.client_axes)
            model = self.model
            comp = self.compression

            def local_fn(params, xb, yb, wb, mask_l, p, eta, wmasks_l):
                deltas = self._deltas(params, xb, yb, wb, eta)
                ids = model.layer_ids(params)
                if hetero:
                    with jax.named_scope("fold"):
                        num, den = hetero_overlap_partials(deltas, wmasks_l,
                                                           mask_l[:, 0])
                    with jax.named_scope("exchange"):
                        num = jax.lax.psum(num, ax)
                        den = jax.lax.psum(den, ax)
                    with jax.named_scope("fold"):
                        agg = hetero_overlap_mean(num, den)
                elif comp.mode != "none":
                    # each shard's reduction consumes its clients' int8
                    # payload; the psum combines float32 shard partials
                    # (the jnp fold — Pallas inside shard_map is not
                    # supported in interpret mode)
                    with jax.named_scope("exchange"):
                        counts = jax.lax.psum(mask_l.sum(0), ax)
                    with jax.named_scope("fold"):
                        payload = compress_deltas(deltas, ids, comp)
                        part = aggregate_compressed(
                            payload, params, ids, mask_l, p, cfg=comp,
                            counts=counts, bias_correct=bias_correct,
                            agg_impl="jnp")
                    with jax.named_scope("exchange"):
                        agg = jax.lax.psum(part, ax)
                else:
                    # scoped inside: fold, with its psums as exchange
                    agg = aggregate_grads_local(deltas, ids, mask_l, p, ax,
                                                bias_correct=bias_correct)
                with jax.named_scope("server_apply"):
                    sub = (_sub32 if comp.mode != "none" and not hetero
                           else lambda w, d: w - d)
                    return jax.tree.map(sub, params, agg)

            spec_c = P(ax)      # leading client axis sharded over batch axes
            spec_r = P()        # replicated
            wm_spec = spec_c if hetero else spec_r
            self._steps[key] = jax.jit(jax.shard_map(
                local_fn, mesh=mesh,
                in_specs=(spec_r, spec_c, spec_c, spec_c, spec_c, spec_r,
                          spec_r, wm_spec),
                out_specs=spec_r, check_vma=False),
                donate_argnums=self._donate_params)
        return self._steps[key]

    def run_round(self, params, xb, yb, wb, mask, p, eta, *,
                  bias_correct, wmasks=None, ctx=None):
        self._check_rule(wmasks)
        step = self._step(bool(bias_correct), wmasks is not None)
        return self._traced_fused(step, params, xb, yb, wb, mask, p, eta,
                                  wmasks)

    def describe(self):
        return {**super().describe(), "shards": self.n_shards,
                "mesh_axes": list(self.mesh.axis_names)}


class TemporalBackend(ExecutionBackend):
    """Clients as grad-accumulation microbatches: ``lax.scan`` over the
    cohort axis, folding the Eq. 5 coefficients into the accumulation.

    This is the big-arch LM client layout of
    ``repro.launch.steps.make_train_step(mode="temporal")`` hoisted into the
    unified runtime: each scan step runs ONE client's local update and adds
    its coefficient-weighted delta (:func:`repro.core.aggregation.
    weight_by_layer`) into a single f32 accumulator, so peak memory is one
    delta pytree regardless of cohort size — the layout required for the
    480B-class architectures. HeteroFL rounds accumulate the width-overlap
    (num, den) partials instead and finish with
    :func:`repro.core.aggregation.hetero_overlap_mean`.
    """

    name = "temporal"

    def __init__(self, model, *, local_iters: int = 1, l2: float = 0.0,
                 donate: bool = True, compression=None,
                 agg_impl: str = "jnp"):
        super().__init__(model, local_iters=local_iters, l2=l2, donate=donate,
                         compression=compression, agg_impl=agg_impl)
        self._steps: dict[tuple, Callable] = {}

    def _step(self, bias_correct: bool, hetero: bool) -> Callable:
        key = (bias_correct, hetero)
        if key not in self._steps:
            model = self.model

            def delta_u(params, x_u, y_u, w_u, eta):
                with jax.named_scope("local_train"):
                    return local_update(model.loss, params, x_u, y_u, w_u,
                                        eta, local_iters=self.local_iters,
                                        l2=self.l2)

            def step(params, xb, yb, wb, mask, p, eta, wmasks):
                ids = model.layer_ids(params)
                with jax.named_scope("fold"):
                    zeros32 = jax.tree.map(
                        lambda w: jnp.zeros(w.shape, jnp.float32), params)
                if hetero:
                    part = mask[:, 0]                       # (U,)

                    def body(acc, inp):
                        x_u, y_u, w_u, pt_u, wm_u = inp
                        d = delta_u(params, x_u, y_u, w_u, eta)
                        with jax.named_scope("fold"):
                            num, den = acc
                            num = jax.tree.map(
                                lambda n, dd, wm: n + pt_u * wm
                                * dd.astype(jnp.float32), num, d, wm_u)
                            den = jax.tree.map(
                                lambda dn, wm: dn + pt_u * wm, den, wm_u)
                        return (num, den), None

                    (num, den), _ = jax.lax.scan(
                        body, (zeros32, zeros32), (xb, yb, wb, part, wmasks))
                    with jax.named_scope("fold"):
                        agg = hetero_overlap_mean(num, den)
                else:
                    with jax.named_scope("fold"):
                        coeffs = layer_coefficients(mask, p,
                                                    bias_correct=bias_correct)
                    comp = self.compression

                    if comp.mode != "none":
                        # one client per scan step: quantize the delta to
                        # its wire form, then dequant+weight+accumulate
                        # against this client's GLOBAL-count coefficient
                        # row — peak memory stays one delta pytree
                        def fold_u(acc, d, c_row):
                            d1 = jax.tree.map(
                                lambda dd: dd.astype(jnp.float32)[None], d)
                            payload = compress_deltas(d1, ids, comp)
                            dw = aggregate_compressed(
                                payload, params, ids, None, None, cfg=comp,
                                coeffs=c_row[None],
                                agg_impl=self.agg_impl)
                            return jax.tree.map(jnp.add, acc, dw)
                    elif self.agg_impl == "pallas":
                        from repro.kernels.ops import adel_aggregate_pallas

                        def fold_u(acc, d, c_row):
                            d1 = jax.tree.map(
                                lambda dd: dd.astype(jnp.float32)[None], d)
                            dw = adel_aggregate_pallas(d1, ids, None, None,
                                                       coeffs=c_row[None])
                            return jax.tree.map(jnp.add, acc, dw)
                    else:
                        def fold_u(acc, d, c_row):
                            dw = jax.tree.map(
                                lambda dd, idl: weight_by_layer(
                                    dd.astype(jnp.float32), idl, c_row),
                                d, ids)
                            return jax.tree.map(jnp.add, acc, dw)

                    def body(acc, inp):
                        x_u, y_u, w_u, c_row = inp
                        d = delta_u(params, x_u, y_u, w_u, eta)
                        with jax.named_scope("fold"):
                            return fold_u(acc, d, c_row), None

                    agg, _ = jax.lax.scan(body, zeros32,
                                          (xb, yb, wb, coeffs))
                with jax.named_scope("server_apply"):
                    return jax.tree.map(
                        lambda w, d: (w.astype(jnp.float32)
                                      - d).astype(w.dtype), params, agg)

            self._steps[key] = jax.jit(step,
                                       donate_argnums=self._donate_params)
        return self._steps[key]

    def run_round(self, params, xb, yb, wb, mask, p, eta, *,
                  bias_correct, wmasks=None, ctx=None):
        self._check_rule(wmasks)
        step = self._step(bool(bias_correct), wmasks is not None)
        return self._traced_fused(step, params, xb, yb, wb, mask, p, eta,
                                  wmasks)


class BufferedBackend(DenseBackend):
    """Semi-async delayed-gradient execution: stragglers' unfinished layers
    are banked and folded into later rounds with staleness decay.

    ADEL-FL's round-synchronous aggregation discards every layer a client
    did not finish by the deadline. Following the delayed-gradient line
    (*Stragglers Are Not Disaster*, arxiv 2102.06329; *TimelyFL*, arxiv
    2304.06947), this backend keeps that work: the straggler continues its
    backward pass past the deadline, and the layers it finishes LATE —
    exactly the complement ``1 - mask`` of the round's contribution mask —
    arrive at the server once the simulated clock reaches

        ``arrival_u = round_end + max(L - z_u, 0) * S_u / P_u + B_u``

    (:func:`repro.core.straggler.late_arrival_delays` — the same
    exponential per-layer clock that makes ``z_u`` Poisson). Each later
    round ``t`` folds every buffered contribution whose arrival the clock
    has passed into the server update with weight ``lam ** tau``
    (``tau = t - work_round >= 1``), through the Eq. 5 layer-wise
    coefficient path: the banked coefficients are
    :func:`repro.core.aggregation.layer_coefficients` evaluated on the
    LATE mask with the late-set zero-contributor probabilities
    :func:`repro.core.straggler.late_p_layers`, so at weight 1 the fold is
    an unbiased estimate of the late set's FedAvg layer mean
    (``tests/test_unbiasedness.py``).

    The carry buffer is a ring of ``buffer_cap`` slots (one per banked
    round), each holding device payloads — float32 delta leaves, or, under
    ``compression=``, the int8 WIRE tuples the on-time reduction already
    computed (the buffer never re-materializes dequantized f32; the fold
    goes through :func:`repro.core.compression.aggregate_compressed` with
    explicit coefficients). Slot payloads are fresh jit outputs and are
    never donated, so they survive the params donation of later round
    steps. Work older than ``max_age`` rounds, or evicted by the ring, is
    dropped (counted in the ``carried_dropped`` ledger column).

    ``lam=0`` (the default) delegates every round to the inherited dense
    step — trajectory-BIT-identical to ``backend="dense"``, which the
    backend-equivalence suite asserts. ``lam>0`` needs the runtime's
    :class:`repro.fl.runtime.RoundContext` (``ctx=``) for the simulated
    clock and straggler rates, and rejects HeteroFL width-mask rounds
    (the width-overlap mean has no late-set analogue).
    """

    name = "buffered"

    def __init__(self, model, *, lam: float = 0.0, max_age: int = 4,
                 buffer_cap: int = 4, local_iters: int = 1, l2: float = 0.0,
                 donate: bool = True, compression=None,
                 agg_impl: str = "jnp"):
        super().__init__(model, local_iters=local_iters, l2=l2,
                         donate=donate, compression=compression,
                         agg_impl=agg_impl)
        if not 0.0 <= float(lam) <= 1.0:
            raise ValueError(f"lam={lam} must be in [0, 1]")
        self.lam = float(lam)
        self.max_age = int(max_age)
        self.buffer_cap = int(buffer_cap)
        self._mains: dict[tuple, Callable] = {}
        self._fold_step = None
        self._slots: list[dict] = []     # FIFO ring of banked rounds
        self.last_carry: dict = {}

    @property
    def needs_ctx(self) -> bool:        # type: ignore[override]
        return self.lam > 0.0

    def reset_state(self) -> None:
        self._slots = []
        self.last_carry = {}

    def describe(self):
        return {**super().describe(), "lam": self.lam,
                "max_age": self.max_age, "buffer_cap": self.buffer_cap}

    # jit steps ---------------------------------------------------------
    def _main(self, bias_correct: bool, bank: bool) -> Callable:
        """Fused local-train + on-time Eq. 5 aggregate, optionally also
        returning the round's bankable payload (the wire format under
        compression, float32 delta leaves otherwise)."""
        key = (bias_correct, bank)
        if key not in self._mains:
            comp = self.compression

            def step(params, xb, yb, wb, mask, p, eta):
                deltas = self._deltas(params, xb, yb, wb, eta)
                ids = self.model.layer_ids(params)
                banked = None
                if comp.mode != "none":
                    payload = compress_deltas(deltas, ids, comp)
                    agg = aggregate_compressed(
                        payload, params, ids, mask, p, cfg=comp,
                        bias_correct=bias_correct, agg_impl=self.agg_impl)
                    banked = payload       # the SAME int8 wire tuples
                else:
                    banked = jax.tree.map(
                        lambda d: d.astype(jnp.float32), deltas)
                    if self.agg_impl == "pallas":
                        from repro.kernels.ops import adel_aggregate_pallas
                        agg = adel_aggregate_pallas(
                            deltas, ids, mask, p, bias_correct=bias_correct)
                    else:
                        agg = aggregate_grads(deltas, ids, mask, p,
                                              bias_correct=bias_correct)
                new = jax.tree.map(_sub32, params, agg)
                return (new, banked) if bank else new

            self._mains[key] = jax.jit(step,
                                       donate_argnums=self._donate_params)
        return self._mains[key]

    def _fold(self) -> Callable:
        """Fold one carry slot into params: ``params - sum_u (c_late[u] *
        w[u]) . delta_u`` — w carries the staleness decay and arrival
        eligibility. Only params is donated; the slot payload may fold
        again (clients of one round arrive at different times)."""
        if self._fold_step is None:
            comp = self.compression

            def fold(params, banked, c_late, w):
                ids = self.model.layer_ids(params)
                coeffs = c_late * w[:, None]
                if comp.mode != "none":
                    agg = aggregate_compressed(
                        banked, params, ids, None, None, cfg=comp,
                        coeffs=coeffs, agg_impl=self.agg_impl)
                elif self.agg_impl == "pallas":
                    from repro.kernels.ops import adel_aggregate_pallas
                    agg = adel_aggregate_pallas(banked, ids, None, None,
                                                coeffs=coeffs)
                else:
                    agg = aggregate_with_coeffs(banked, ids, coeffs)
                return jax.tree.map(_sub32, params, agg)

            self._fold_step = jax.jit(fold,
                                      donate_argnums=self._donate_params)
        return self._fold_step

    # round -------------------------------------------------------------
    def run_round(self, params, xb, yb, wb, mask, p, eta, *,
                  bias_correct, wmasks=None, ctx=None):
        if self.lam == 0.0:
            # exact round-synchronous semantics: the inherited dense step,
            # bit for bit (no carry, no extra jit)
            return super().run_round(params, xb, yb, wb, mask, p, eta,
                                     bias_correct=bias_correct,
                                     wmasks=wmasks)
        if wmasks is not None:
            raise ValueError("buffered backend with lam>0 is incompatible "
                             "with HeteroFL width-mask aggregation")
        if ctx is None:
            raise ValueError("buffered backend with lam>0 needs the "
                             "runtime's RoundContext (ctx=): the carry "
                             "buffer is driven by the simulated clock")
        self._check_rule(wmasks)
        L = int(mask.shape[1])
        U_pad = int(mask.shape[0])
        U_act = int(ctx.U_act)
        t = int(ctx.t)
        mask_h = np.asarray(mask, np.float32)
        depth = mask_h.sum(1)                         # (U_pad,) realized z
        real = np.arange(U_pad) < U_act
        late_rows = real & (depth < L)

        # 1. fold decisions, entirely host-side (slot metadata): which
        #    banked clients' arrivals has the simulated clock passed?
        folds, dropped, stale = [], 0, {}
        for slot in self._slots:
            pend = slot["pending"]
            if not pend.any():
                continue
            tau = t - slot["round"]
            if tau > self.max_age:
                dropped += int(pend.sum())
                pend[:] = False
                continue
            elig = pend & (slot["arrival"] <= float(ctx.sim_end))
            if elig.any():
                w = np.where(elig, np.float32(self.lam) ** tau,
                             np.float32(0.0)).astype(np.float32)
                folds.append((slot, w))
                stale[tau] = stale.get(tau, 0) + int(elig.sum())
                pend &= ~elig

        # 2. this round's late-set coefficients: Eq. 5 on the COMPLEMENT
        #    mask with the late-set zero-contributor probabilities
        bank = bool(late_rows.any())
        if bank:
            late_mask = jnp.asarray((1.0 - mask_h) * real[:, None],
                                    jnp.float32)
            p_late = late_p_layers(jnp.asarray(ctx.lam, jnp.float32), L)
            c_late = layer_coefficients(late_mask, p_late,
                                        bias_correct=bool(bias_correct))

        # 3. the fused train + on-time aggregate (+ bankable payload)
        tracer = self.tracer
        step = self._main(bool(bias_correct), bank)
        with tracer.span("round_step", backend=self.name, fused=True):
            out = step(params, xb, yb, wb, mask, p, eta)
            if tracer.sync:
                jax.block_until_ready(out)
        params, banked = out if bank else (out, None)
        if tracer.active:
            self._count_bytes(params, U_pad)

        # 4. fold every arrived carry slot (params flows through, donated)
        if folds:
            fold = self._fold()
            with tracer.span("aggregate", backend=self.name,
                             carried=sum(int((w > 0).sum())
                                         for _, w in folds)):
                for slot, w in folds:
                    params = fold(params, slot["banked"], slot["c_late"],
                                  jnp.asarray(w))
                if tracer.sync:
                    jax.block_until_ready(params)

        # 5. bank this round's late work (ring eviction drops the oldest)
        if bank:
            delays = late_arrival_delays(depth[:U_act], ctx.layer_s, ctx.B,
                                         L)
            arrival = np.full(U_pad, np.inf, np.float32)
            arrival[:U_act] = float(ctx.sim_end) + np.asarray(delays)
            if len(self._slots) >= self.buffer_cap:
                evicted = self._slots.pop(0)
                dropped += int(evicted["pending"].sum())
            self._slots.append({"round": t, "banked": banked,
                                "c_late": c_late, "arrival": arrival,
                                "pending": late_rows.copy()})

        carried_in = sum(stale.values())
        carried_out = sum(int(s["pending"].sum()) for s in self._slots)
        self.last_carry = {"carried_in": carried_in,
                           "carried_out": carried_out,
                           "carried_dropped": dropped,
                           "stale": stale}
        tracer.count("carried_in", carried_in, backend=self.name)
        tracer.count("carried_out", carried_out, backend=self.name)
        if dropped:
            tracer.count("carried_dropped", dropped, backend=self.name)
        return params


class HierarchicalBackend(ChunkedBackend):
    """Two-tier edge aggregation: per-region partials + one global fold.

    Million-device deployments do not reduce every client update at one
    server: clients report to an edge aggregator for their REGION, and
    only the per-region partial aggregates cross the wide-area network
    (hierarchical FL à la HierFAVG, arxiv 1905.06641). This backend
    reproduces that topology inside the unified runtime:

    1. The padded cohort is partitioned into edge regions. Region ids come
       from the round context (``ctx.regions`` — the population draw's
       ``device_id % Population.regions``); without a context the cohort
       splits into ``regions`` contiguous slices, so the backend works
       under plain ``run_federated`` too.
    2. Each region runs its clients' local updates and computes ONE
       partial aggregate with the chunk machinery —
       :func:`repro.core.aggregation.aggregate_grads_chunk` (or
       ``hetero_overlap_partials`` for HeteroFL rounds) evaluated against
       the GLOBAL per-layer contributor counts, so summing the partials
       over regions is exactly the flat Eq. 5 fold on the whole cohort.
       Under ``compression=``, each region's int8 wire payload is
       dequantized+weighted+accumulated region-locally
       (:func:`repro.core.compression.aggregate_compressed`) — only the
       float32 partial crosses region boundaries, never per-client wire
       tuples.
    3. The server applies the summed partials in one donated step.

    Regions are gathered through padded index maps (region width rounded
    up to a multiple of 8, pad slots pointing at row 0 with a validity
    column zeroing their mask rows), so jit retraces at most once per
    distinct padded region width rather than per region census.

    A single-region round (``regions=1``, or every sampled device in one
    region) delegates to the dense step — bit-identical to
    ``backend="dense"``, which ``tests/test_population.py`` asserts.
    ``last_regions`` exposes the round's region census to the runtime's
    ledger (``regions`` / ``region_max`` / ``region_pad`` columns).
    """

    name = "hierarchical"
    needs_ctx = True

    def __init__(self, model, *, regions: int = 4, chunk_size: int = 16,
                 local_iters: int = 1, l2: float = 0.0, donate: bool = True,
                 compression=None, agg_impl: str = "jnp"):
        super().__init__(model, chunk_size=chunk_size,
                         local_iters=local_iters, l2=l2, donate=donate,
                         compression=compression, agg_impl=agg_impl)
        self.regions = max(int(regions), 1)
        self.last_regions: dict = {}

    def cohort_pad(self, U: int) -> int:
        # regions pad internally (multiple-of-8 gathers); the cohort axis
        # itself needs no chunk-multiple padding
        return int(U)

    def reset_state(self) -> None:
        self.last_regions = {}

    def describe(self):
        return {**super().describe(), "regions": self.regions}

    def _region_groups(self, ctx, U: int) -> list[np.ndarray]:
        """Per-region member indices into the padded cohort axis.

        Pad rows (>= U_act) keep the region id of the fallback split or
        id 0; their mask rows are all-zero either way, so they contribute
        nothing regardless of which region gathers them.
        """
        ra = getattr(ctx, "regions", None) if ctx is not None else None
        if ra is not None:
            ra = np.asarray(ra, np.int64)
            rid = np.zeros(U, np.int64)
            rid[:min(len(ra), U)] = ra[:U]
        else:
            rid = (np.arange(U) * self.regions) // max(U, 1)
        return [np.flatnonzero(rid == g) for g in np.unique(rid)]

    def run_round(self, params, xb, yb, wb, mask, p, eta, *,
                  bias_correct, wmasks=None, ctx=None):
        self._check_rule(wmasks)
        U = int(mask.shape[0])
        groups = self._region_groups(ctx, U)
        if len(groups) <= 1:
            self.last_regions = {"regions": 1, "region_max": U,
                                 "region_pad": U}
            return self._dense.run_round(params, xb, yb, wb, mask, p, eta,
                                         bias_correct=bias_correct,
                                         wmasks=wmasks)
        rmax = max(len(g) for g in groups)
        r_pad = max(-(-rmax // 8) * 8, 8)
        self.last_regions = {"regions": len(groups), "region_max": rmax,
                             "region_pad": r_pad}
        counts = mask.sum(0)              # (L,) GLOBAL contributor counts
        tracer = self.tracer
        hetero = wmasks is not None
        gathers = []
        for g in groups:
            idx = np.zeros(r_pad, np.int64)
            idx[:len(g)] = g
            valid = np.zeros((r_pad, 1), np.float32)
            valid[:len(g)] = 1.0
            gathers.append((idx, jnp.asarray(valid)))

        if self.compression.mode != "none":
            payload_step = self._payload()
            fold = self._fold(bool(bias_correct))
            acc = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32),
                               params)
            for j, (idx, valid) in enumerate(gathers):
                m_r = jnp.asarray(mask)[idx] * valid
                with tracer.span("local_train", backend=self.name,
                                 region=j):
                    payload = payload_step(params, xb[idx], yb[idx],
                                           wb[idx], eta)
                    if tracer.sync:
                        jax.block_until_ready(payload)
                if tracer.active:
                    self._count_bytes(params, len(groups[j]))
                acc = fold(acc, params, payload, m_r, p, counts)
            with tracer.span("aggregate", backend=self.name,
                             regions=len(groups)):
                out = self._apply32(params, acc)
                if tracer.sync:
                    jax.block_until_ready(out)
            return out

        step = self._chunk_step(bool(bias_correct), hetero)
        num = den = agg = None
        for j, (idx, valid) in enumerate(gathers):
            m_r = jnp.asarray(mask)[idx] * valid
            wm_r = (None if not hetero
                    else jax.tree.map(lambda m: m[idx], wmasks))
            with tracer.span("local_train", backend=self.name, region=j):
                part = step(params, xb[idx], yb[idx], wb[idx], m_r, p, eta,
                            counts, wm_r)
                if tracer.sync:
                    jax.block_until_ready(part)
            if tracer.active:
                self._count_bytes(params, len(groups[j]))
            if hetero:
                n_p, d_p = part
                num = n_p if num is None else jax.tree.map(jnp.add, num, n_p)
                den = d_p if den is None else jax.tree.map(jnp.add, den, d_p)
            else:
                agg = part if agg is None else jax.tree.map(jnp.add, agg,
                                                            part)
        with tracer.span("aggregate", backend=self.name,
                         regions=len(groups)):
            out = (self._apply_hetero(params, num, den) if hetero
                   else self._apply(params, agg))
            if tracer.sync:
                jax.block_until_ready(out)
        return out


def make_backend(backend=None, model=None, *, exec: ExecSpec | None = None,
                 chunk_size: int | None = None, mesh=None,
                 local_iters: int | None = None, l2: float | None = None,
                 donate: bool | None = None, compression=None,
                 agg_impl: str | None = None, lam: float | None = None,
                 max_age: int | None = None, buffer_cap: int | None = None,
                 regions: int | None = None) -> ExecutionBackend:
    """Build an :class:`ExecutionBackend` from an
    :class:`repro.fl.spec.ExecSpec` (``exec=``, or an ExecSpec as the
    first positional argument) or from the legacy kwargs — both funnel
    through :meth:`ExecSpec.resolve`, so the two call forms are
    equivalent. An :class:`ExecutionBackend` instance passes through
    unchanged.

    Legacy kwargs default to None (= the spec's value): ``backend`` names
    one of :data:`BACKENDS`; ``compression`` is a
    :mod:`repro.core.compression` spec (None | mode string |
    ``(mode, top_k)`` | :class:`CompressionConfig`) selecting the
    client->server wire format the reduction consumes; ``agg_impl``
    (``"jnp" | "pallas"``) picks the aggregation implementation — "pallas"
    routes stacked-layer folds through the fused kernels (``adel_agg`` /
    ``adel_agg_q8``, interpret mode on CPU) on the dense, temporal and
    buffered backends and on every compressed non-shard_map path;
    ``lam`` / ``max_age`` / ``buffer_cap`` are the buffered backend's
    staleness knobs. Knobs the selected backend would silently ignore
    warn (or raise, under ``REPRO_EXEC_STRICT=1``) via
    :meth:`ExecSpec.validate`.
    """
    if isinstance(backend, ExecutionBackend):
        return backend
    if isinstance(backend, ExecSpec):
        exec, backend = (backend if exec is None else exec), None
    legacy = dict(backend=backend, chunk_size=chunk_size, mesh=mesh,
                  local_iters=local_iters, l2=l2, donate=donate,
                  compression=compression, agg_impl=agg_impl, lam=lam,
                  max_age=max_age, buffer_cap=buffer_cap, regions=regions)
    has_legacy = any(v is not None for v in legacy.values())
    # a complete ExecSpec was validated by the resolve() that built it;
    # re-validate only when legacy kwargs modify it
    spec = ExecSpec.resolve(exec, validate=has_legacy or exec is None,
                            **legacy)
    if isinstance(spec.backend, ExecutionBackend):
        return spec.backend
    kw = spec.backend_kwargs()
    if spec.backend == "dense":
        return DenseBackend(model, **kw)
    if spec.backend == "chunked":
        return ChunkedBackend(model, chunk_size=spec.chunk_size, **kw)
    if spec.backend == "shard_map":
        return ShardMapBackend(model, mesh=spec.mesh, **kw)
    if spec.backend == "temporal":
        return TemporalBackend(model, **kw)
    if spec.backend == "buffered":
        return BufferedBackend(model, lam=spec.lam, max_age=spec.max_age,
                               buffer_cap=spec.buffer_cap, **kw)
    if spec.backend == "hierarchical":
        return HierarchicalBackend(model, regions=spec.regions,
                                   chunk_size=spec.chunk_size, **kw)
    raise ValueError(f"unknown backend {spec.backend!r}; known: {BACKENDS}")
