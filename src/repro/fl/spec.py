"""``ExecSpec`` — ONE execution spec for every federated entry point.

Before this module, the tuple ``backend / chunk_size / mesh / local_iters /
l2 / donate / compression / agg_impl`` was copy-pasted into every front-end
signature (``make_backend``, ``run_federated``, ``run_fleet``,
``run_training``) and every CLI grew its own ``--backend/--compression/...``
flag block. :class:`ExecSpec` bundles the whole tuple — plus the buffered
(semi-async) backend's staleness knobs ``lam`` / ``max_age`` /
``buffer_cap`` — into one frozen dataclass that is:

* accepted as ``exec=`` by every entry point, with the old kwargs kept as
  deprecated aliases resolved through the single parsing path
  :meth:`ExecSpec.resolve` (bit-identical trajectories either way);
* the single source of the CLI surface: :meth:`ExecSpec.add_cli_args`
  installs one shared argparse group and :meth:`ExecSpec.from_cli` reads it
  back, so ``python -m repro.fleet.scenarios`` and ``repro.launch.train``
  share one flag block;
* where knob validation lives: :meth:`ExecSpec.resolve` warns on knob
  combinations the selected backend silently ignores (``chunk_size`` on a
  non-chunked backend, ``mesh`` off shard_map, staleness knobs off the
  buffered backend, ``agg_impl="pallas"`` under shard_map) — or raises,
  under ``strict=True`` / ``REPRO_EXEC_STRICT=1``.

The canonical backend/agg-impl name tuples live here (re-exported by
:mod:`repro.fl.backends`, which imports this module) so the spec never
needs a circular import to validate itself.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Optional

from repro.core.compression import (MODES as COMPRESSION_MODES,
                                    CompressionConfig, make_compression)

__all__ = ["BACKENDS", "AGG_IMPLS", "PIPELINES", "ExecSpec"]

# dense: one vmap over the cohort; chunked: sequential software psum;
# shard_map: a real client mesh axis; temporal: grad-accumulation scan;
# buffered: dense + a staleness-weighted delayed-gradient carry buffer;
# hierarchical: per-edge-region partial aggregates + one global Eq. 5 fold
BACKENDS = ("dense", "chunked", "shard_map", "temporal", "buffered",
            "hierarchical")

AGG_IMPLS = ("jnp", "pallas")

# serial: the classic loop (plan round t, run round t, repeat);
# prefetch: one-round-lookahead driver — round t+1's host phases run on a
# worker thread while round t's device step is in flight (see
# repro.fl.runtime for the execution timeline; trajectories bit-identical)
PIPELINES = ("serial", "prefetch")

# legacy-kwarg aliases `resolve` understands, in ExecSpec field order
_FIELDS = ("backend", "chunk_size", "mesh", "local_iters", "l2", "donate",
           "compression", "agg_impl", "lam", "max_age", "buffer_cap",
           "regions", "pipeline")


@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """How federated rounds execute: backend + its knobs, in one value.

    ``backend`` selects the :mod:`repro.fl.backends` execution backend;
    ``chunk_size`` / ``mesh`` configure the chunked / shard_map backends;
    ``local_iters`` / ``l2`` shape the client-side local update;
    ``donate`` controls params-buffer donation in the round steps;
    ``compression`` is the client->server wire format
    (:mod:`repro.core.compression` spec — normalized to a
    :class:`CompressionConfig` on construction); ``agg_impl`` picks the
    Eq. 5 fold implementation (``"jnp"`` or the fused Pallas kernels).

    The staleness knobs drive the ``buffered`` semi-async backend: a
    straggler's unfinished layers enter a server-side carry buffer and are
    folded into a later round with weight ``w(tau) = lam ** tau`` (``tau``
    = rounds of staleness). ``lam=0`` (default) is exact round-synchronous
    semantics — bit-identical to ``backend="dense"``. ``max_age`` drops
    buffered work older than that many rounds; ``buffer_cap`` bounds the
    carry ring buffer (one slot per recent round).

    ``regions`` is the ``hierarchical`` backend's FALLBACK edge-region
    count: when the round context carries no per-device region ids (no
    :class:`repro.fleet.population.Population` behind the cohort source),
    the cohort splits into this many contiguous regions. Cohort region
    ids from a population (``device id % population.regions``) always take
    precedence. ``regions=1`` degenerates to the dense fold, bit-exactly.
    """

    backend: str = "dense"
    chunk_size: int = 16
    mesh: Any = None
    local_iters: int = 1
    l2: float = 0.0
    donate: bool = True
    compression: CompressionConfig = CompressionConfig()
    agg_impl: str = "jnp"
    # buffered (semi-async) staleness knobs
    lam: float = 0.0
    max_age: int = 4
    buffer_cap: int = 4
    # hierarchical backend: fallback edge-region count (see class docstring)
    regions: int = 4
    # round-driver pipelining: "serial" or "prefetch" (one-round lookahead;
    # bit-identical trajectories — see repro.fl.runtime's timeline docs)
    pipeline: str = "serial"

    def __post_init__(self):
        # normalize the legacy compression spec forms (None | mode string |
        # (mode, top_k)) so equality and hashing see one canonical value
        object.__setattr__(self, "compression",
                           make_compression(self.compression))
        if self.backend not in BACKENDS and not hasattr(self.backend,
                                                        "run_round"):
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"known: {BACKENDS}")
        if self.agg_impl not in AGG_IMPLS:
            raise ValueError(f"unknown agg_impl {self.agg_impl!r}; "
                             f"known: {AGG_IMPLS}")
        if not 0.0 <= float(self.lam) <= 1.0:
            raise ValueError(f"staleness decay lam={self.lam} must be in "
                             f"[0, 1] (w(tau) = lam ** tau)")
        if int(self.max_age) < 1 or int(self.buffer_cap) < 1:
            raise ValueError("max_age and buffer_cap must be >= 1")
        if int(self.regions) < 1:
            raise ValueError(f"regions must be >= 1, got {self.regions}")
        if self.pipeline not in PIPELINES:
            raise ValueError(f"unknown pipeline {self.pipeline!r}; "
                             f"known: {PIPELINES}")

    # ------------------------------------------------------------------
    @classmethod
    def resolve(cls, exec: Optional["ExecSpec"] = None, *,
                base: Optional["ExecSpec"] = None,
                strict: Optional[bool] = None,
                validate: bool = True, **legacy) -> "ExecSpec":
        """THE parsing path every entry point funnels through.

        Starts from ``exec`` (or ``base``, or the defaults), overlays any
        legacy kwarg that was explicitly passed (non-None), and validates
        the result. Entry points keep their old kwargs with ``None``
        sentinels, so ``run_federated(backend="chunked")`` and
        ``run_federated(exec=ExecSpec(backend="chunked"))`` resolve to the
        same spec — and the same trajectory.

        Inapplicable knob combinations (a non-default ``chunk_size`` on a
        backend that never chunks, ``mesh`` off shard_map, staleness knobs
        off ``buffered``, ``agg_impl="pallas"`` under shard_map) emit a
        :class:`UserWarning`; with ``strict=True`` (or the
        ``REPRO_EXEC_STRICT=1`` environment variable) they raise instead —
        extending the HeteroFL+compression guard that already rejects
        un-foldable combinations at round time.
        """
        unknown = set(legacy) - set(_FIELDS)
        if unknown:
            raise TypeError(f"unknown execution kwargs {sorted(unknown)}; "
                            f"known: {_FIELDS}")
        spec = exec if exec is not None else (base if base is not None
                                              else cls())
        if not isinstance(spec, cls):
            raise TypeError(f"exec= expects an ExecSpec, got {type(spec)}")
        overrides = {k: v for k, v in legacy.items() if v is not None}
        if overrides:
            spec = dataclasses.replace(spec, **overrides)
        if validate:
            spec.validate(strict=strict)
        return spec

    def validate(self, *, strict: Optional[bool] = None) -> "ExecSpec":
        """Warn (or raise, under strict) on knobs the backend ignores."""
        if strict is None:
            strict = bool(os.environ.get("REPRO_EXEC_STRICT"))
        defaults = ExecSpec()
        issues = []
        if self.chunk_size != defaults.chunk_size and \
                self.backend != "chunked":
            issues.append(f"chunk_size={self.chunk_size} is ignored by "
                          f"backend={self.backend!r} (chunked only)")
        if self.mesh is not None and self.backend != "shard_map":
            issues.append(f"mesh= is ignored by backend={self.backend!r} "
                          f"(shard_map only)")
        if self.backend != "buffered" and (
                self.lam != defaults.lam or
                self.max_age != defaults.max_age or
                self.buffer_cap != defaults.buffer_cap):
            issues.append(f"staleness knobs (lam={self.lam}, "
                          f"max_age={self.max_age}, "
                          f"buffer_cap={self.buffer_cap}) are ignored by "
                          f"backend={self.backend!r} (buffered only)")
        if self.agg_impl == "pallas" and self.backend == "shard_map":
            issues.append("agg_impl='pallas' is ignored under shard_map "
                          "(shard-local folds run the jnp path)")
        if self.regions != defaults.regions and \
                self.backend != "hierarchical":
            issues.append(f"regions={self.regions} is ignored by "
                          f"backend={self.backend!r} (hierarchical only)")
        for msg in issues:
            if strict:
                raise ValueError(f"ExecSpec: {msg}")
            warnings.warn(f"ExecSpec: {msg}", UserWarning, stacklevel=3)
        return self

    # ------------------------------------------------------------------
    def backend_kwargs(self) -> dict:
        """Constructor kwargs shared by every execution backend."""
        return dict(local_iters=self.local_iters, l2=self.l2,
                    donate=self.donate, compression=self.compression,
                    agg_impl=self.agg_impl)

    def as_dict(self) -> dict:
        """JSON-friendly description (mesh elided to its axis names)."""
        d = {f: getattr(self, f) for f in _FIELDS}
        d["compression"] = dataclasses.asdict(self.compression)
        if self.mesh is not None:
            d["mesh"] = list(getattr(self.mesh, "axis_names", ("?",)))
        return d

    # ------------------------------------------------------------------
    # one CLI surface, derived from the spec (shared by
    # `python -m repro.fleet.scenarios` and `python -m repro.launch.train`)
    @staticmethod
    def add_cli_args(parser) -> None:
        """Install the shared execution-spec argparse group.

        Every flag defaults to None (= keep the resolved spec's value), so
        front-ends can layer CLI overrides on top of their own defaults —
        scenarios on the FleetConfig's spec, the LM driver on ``dense``.
        """
        g = parser.add_argument_group(
            "execution", "execution backend spec (repro.fl.spec.ExecSpec); "
            "unset flags keep the front-end's resolved defaults")
        g.add_argument("--backend", default=None, choices=list(BACKENDS),
                       help="execution backend (repro.fl.backends); "
                            "temporal is the big-arch grad-accumulation "
                            "layout, buffered the semi-async delayed-"
                            "gradient backend")
        g.add_argument("--chunk-size", type=int, default=None,
                       help="client-shard axis chunk (chunked backend)")
        g.add_argument("--no-donate", dest="donate", action="store_false",
                       default=None,
                       help="disable params-buffer donation in round steps")
        g.add_argument("--compression", default=None,
                       choices=list(COMPRESSION_MODES),
                       help="client->server wire compression "
                            "(repro.core.compression): int8 symmetric "
                            "quantization or topk8 sparsification; the "
                            "backend's reduction consumes the compressed "
                            "payload and the solver prices B_u by the "
                            "wire ratio")
        g.add_argument("--topk-frac", type=float, default=None,
                       help="kept fraction per (client, layer) in topk8 "
                            "mode")
        g.add_argument("--agg-impl", default=None, choices=list(AGG_IMPLS),
                       help="aggregation implementation: pallas routes the "
                            "Eq. 5 fold through the fused kernels "
                            "(adel_agg / adel_agg_q8; interpret mode on "
                            "CPU)")
        g.add_argument("--lam", type=float, default=None,
                       help="buffered backend: staleness decay of delayed "
                            "gradients, w(tau) = lam**tau (0 = exact "
                            "round-synchronous semantics)")
        g.add_argument("--max-age", type=int, default=None,
                       help="buffered backend: drop carried work older "
                            "than this many rounds")
        g.add_argument("--buffer-cap", type=int, default=None,
                       help="buffered backend: carry ring-buffer slots "
                            "(one per recent round)")
        g.add_argument("--pipeline", default=None, choices=list(PIPELINES),
                       help="round-driver pipelining: prefetch overlaps "
                            "round t+1's host planning/stacking with round "
                            "t's device step and AOT-warms the round/eval "
                            "steps before round 0 (trajectories stay "
                            "bit-identical to serial)")

    @classmethod
    def from_cli(cls, args, *, base: Optional["ExecSpec"] = None,
                 strict: Optional[bool] = None) -> "ExecSpec":
        """Resolve the spec from parsed :meth:`add_cli_args` flags."""
        compression = None
        if args.compression is not None:
            compression = (args.compression if args.topk_frac is None
                           else (args.compression, args.topk_frac))
        elif args.topk_frac is not None and base is not None:
            compression = dataclasses.replace(base.compression,
                                              top_k=float(args.topk_frac))
        return cls.resolve(base=base, strict=strict,
                           backend=args.backend,
                           chunk_size=args.chunk_size,
                           donate=args.donate,
                           compression=compression,
                           agg_impl=args.agg_impl,
                           lam=args.lam, max_age=args.max_age,
                           buffer_cap=args.buffer_cap,
                           pipeline=getattr(args, "pipeline", None))
