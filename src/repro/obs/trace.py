"""Lightweight tracing core for the round runtime.

A :class:`Tracer` records three kinds of structured events:

* **phase spans** — nestable, monotonic-clock timed sections
  (``plan`` / ``cohort`` / ``stack`` / ``round_step`` / ``local_train`` /
  ``aggregate`` / ``eval`` / ``replan`` / ``checkpoint``), emitted on span
  exit with duration, nesting depth, parent phase, and a global sequence
  number;
* **typed counters** — monotonically accumulated counts (padded-vs-real
  batch elements, bytes aggregated per backend, bytes copied from host
  memory to the device);
* **ledger events** — one ``kind="round"`` record per executed round
  carrying the clock-model ledger fields (:mod:`repro.obs.ledger`:
  deadline ``T_t`` vs simulated round time vs measured host wall time,
  predicted vs realized straggler depths).

Every record is a plain dict fanned out to the attached sinks:
:class:`JsonlSink` appends JSON lines to a file (the
``python -m repro.obs.timeline`` input), :class:`MemorySink` keeps them in
a list (tests, in-process consumers).

Every phase span, with or without a tracer, also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<phase>`` (:func:`annotate`,
the one place that names them), so a profiler trace shows the program's
own phases on the device trace's clock. With no profiler running an
annotation costs well under a microsecond.

The default tracer everywhere is the :data:`NULL_TRACER` singleton — it
records nothing, ``active`` is False so instrumented call sites skip
record construction entirely, and trajectories are bit-identical with or
without it (tracing never touches PRNG keys or numerics). An active
tracer with ``sync=True`` (the default) adds ``jax.block_until_ready``
fences so span durations and the ledger's wall times measure device work
instead of async dispatch; ``sync=False`` adds none, converts no device
value, and leaves the wall fields empty, for runs whose device timeline
comes from a profiler trace.

All span timing flows through :func:`now` (``time.perf_counter``) — the
monotonic clock benchmark call sites share, so recorded durations are
NTP-proof.
"""
from __future__ import annotations

import functools
import io
import json
import os
import time
from typing import Any, Callable, Optional

from jax.profiler import TraceAnnotation, annotate_function

__all__ = ["now", "PHASES", "annotate", "annotated", "Sink", "MemorySink",
           "JsonlSink", "Span", "Tracer", "NullTracer", "NULL_TRACER",
           "make_tracer"]

# canonical phase order of one federated round (timeline rendering order);
# warm_up is the pre-round-0 AOT trace/compile/execute of the round + eval
# steps (prefetch pipeline), charged to the round that triggered it;
# round_step is the fused backends' single dispatch (train + fold + apply),
# local_train / aggregate the chunked backends' separate calls
PHASES = ("warm_up", "cohort", "replan", "plan", "stack", "round_step",
          "local_train", "aggregate", "eval", "checkpoint")


def annotate(name: str, **attrs) -> TraceAnnotation:
    """The profiler's host span of phase ``name``: a
    ``jax.profiler.TraceAnnotation`` called ``repro.<name>``, carrying
    ``attrs``. Besides the phase spans, the runtime opens ``repro.run``
    around a run, ``repro.round`` (``round=t``) around each round,
    ``repro.prefetch`` on the worker that plans the next round, and the
    policies ``repro.draw`` around the straggler draw."""
    return TraceAnnotation(f"repro.{name}", **attrs)


def annotated(name: str) -> Callable:
    """Decorator: every call of the function runs under
    :func:`annotate` ``(name)``."""
    return functools.partial(annotate_function, name=f"repro.{name}")


def now() -> float:
    """Monotonic timestamp in seconds (``time.perf_counter``).

    The single timing primitive for spans AND benchmark wall-clocks:
    ``time.time()`` can jump under NTP slew, so durations computed from it
    are not trustworthy on shared CI runners.
    """
    return time.perf_counter()


def _json_default(o):
    """Best-effort JSON coercion for numpy scalars / arrays in records."""
    if hasattr(o, "item"):
        return o.item()
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


class Sink:
    """Consumer of telemetry records (plain dicts)."""

    def emit(self, rec: dict) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemorySink(Sink):
    """Keeps every record in ``self.records`` (tests, in-process readers)."""

    def __init__(self):
        self.records: list[dict] = []

    def emit(self, rec: dict) -> None:
        self.records.append(rec)


class JsonlSink(Sink):
    """Appends one JSON object per record to ``path`` (created eagerly so a
    crashed run still leaves a parseable prefix)."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._f: Optional[io.TextIOBase] = open(path, "w")

    def emit(self, rec: dict) -> None:
        if self._f is not None:
            self._f.write(json.dumps(rec, default=_json_default) + "\n")

    def close(self) -> None:
        if self._f is not None:
            self._f.flush()
            self._f.close()
            self._f = None


class Span:
    """One nestable timed phase; emitted as a record when the span exits."""

    __slots__ = ("_tr", "name", "attrs", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tr = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "Span":
        self._tr._stack.append(self.name)
        self._ann = annotate(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0 = self._tr.clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        t1 = tr.clock()
        self._ann.__exit__(*exc)
        tr._stack.pop()
        rec = {"kind": "span", "name": self.name,
               "round": tr._round,
               "t0": self.t0, "dur_s": t1 - self.t0,
               "depth": len(tr._stack),
               "parent": tr._stack[-1] if tr._stack else None,
               "seq": tr._next_seq()}
        if self.attrs:
            rec.update(self.attrs)
        tr._note_span(rec)
        tr._emit(rec)
        return False


class Tracer:
    """Collects spans / counters / events and fans them out to sinks, while
    aggregating an in-memory summary (per-phase totals, counter totals, the
    per-round clock-model ledger).

    ``clock`` is injectable for deterministic tests; it defaults to the
    monotonic :func:`now`. ``sync`` (default True) lets instrumented call
    sites block on device results so spans and the ledger's wall fields
    measure device work; with ``sync=False`` they never block and never
    convert a device value, and the records that need device values
    (ledger rows, eval results) are emitted when the runtime next
    materializes them for its own reasons.
    """

    active = True

    def __init__(self, sinks: Any = (), *, clock: Callable[[], float] = now,
                 sync: bool = True):
        if isinstance(sinks, Sink):
            sinks = (sinks,)
        self.sinks: list[Sink] = list(sinks)
        self.clock = clock
        self.sync = bool(sync)
        self._stack: list[str] = []
        self._seq = 0
        self._round: Optional[int] = None
        # aggregated summary state
        self.phase_totals: dict[str, dict] = {}
        self.counters: dict[str, float] = {}
        self.rounds: list[dict] = []       # kind="round" ledger records

    # ------------------------------------------------------------------
    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _emit(self, rec: dict) -> None:
        for s in self.sinks:
            s.emit(rec)

    def _note_span(self, rec: dict) -> None:
        agg = self.phase_totals.setdefault(rec["name"],
                                           {"count": 0, "total_s": 0.0})
        agg["count"] += 1
        agg["total_s"] += rec["dur_s"]

    # ------------------------------------------------------------------
    def set_round(self, t: Optional[int]) -> None:
        """Stamp subsequent records with round number ``t`` (1-based)."""
        self._round = t

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def span_record(self, name: str, t0: float, dur_s: float,
                    **attrs) -> None:
        """Emit a span measured elsewhere (the prefetch worker times round
        t+1's host phases off-thread and the runtime emits them at consume
        time, so sink writes and summary aggregation stay single-threaded).
        Identical record shape to a :class:`Span` exit; nesting fields
        reflect the emission point (the worker runs phases un-nested)."""
        rec = {"kind": "span", "name": name, "round": self._round,
               "t0": t0, "dur_s": dur_s,
               "depth": len(self._stack),
               "parent": self._stack[-1] if self._stack else None,
               "seq": self._next_seq()}
        if attrs:
            rec.update(attrs)
        self._note_span(rec)
        self._emit(rec)

    def count(self, name: str, value: float = 1, **attrs) -> None:
        self.counters[name] = self.counters.get(name, 0) + value
        self._emit({"kind": "count", "name": name, "round": self._round,
                    "value": value, **attrs})

    def event(self, kind: str, **fields) -> None:
        rec = {"kind": kind, "round": self._round, **fields}
        if kind == "round":
            self.rounds.append(rec)
        self._emit(rec)

    # ------------------------------------------------------------------
    def summary(self) -> dict:
        """JSON-ready aggregate: per-phase wall totals, counter totals, the
        per-round clock-model ledger, and its drift statistics
        (:func:`repro.obs.ledger.drift_summary`)."""
        from repro.obs.ledger import drift_summary
        phases = {name: {"count": int(d["count"]),
                         "total_s": round(float(d["total_s"]), 6)}
                  for name, d in self.phase_totals.items()}
        ledger = [{k: v for k, v in r.items() if k != "kind"}
                  for r in self.rounds]
        return {"phases": phases,
                "counters": {k: (int(v) if float(v).is_integer() else
                                 round(float(v), 6))
                             for k, v in self.counters.items()},
                "ledger": ledger,
                "drift": drift_summary(self.rounds)}

    def close(self) -> None:
        for s in self.sinks:
            s.close()


class NullTracer:
    """Do-nothing tracer: the zero-overhead default everywhere.

    ``active`` and ``sync`` are False so instrumented call sites skip
    building records / blocking on device results entirely; a span is only
    its profiler annotation (:func:`annotate`), so a profiler trace shows
    the phases of an untraced run too.
    """

    active = False
    sync = False

    def set_round(self, t) -> None:
        pass

    def span(self, name: str, **attrs) -> TraceAnnotation:
        return annotate(name, **attrs)

    def span_record(self, name: str, t0: float, dur_s: float,
                    **attrs) -> None:
        pass

    def count(self, name: str, value: float = 1, **attrs) -> None:
        pass

    def event(self, kind: str, **fields) -> None:
        pass

    def summary(self) -> dict:
        return {}

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


def make_tracer(events: Optional[str] = None, *, sinks: Any = None,
                sync: bool = True) -> Tracer | NullTracer:
    """Convenience constructor for CLIs / benchmarks: a :class:`Tracer`
    (``sync`` as there) writing JSONL to ``events`` (and/or the given
    sinks), or the :data:`NULL_TRACER` when neither is given."""
    out: list[Sink] = []
    if events:
        out.append(JsonlSink(events))
    if sinks is not None:
        out.extend((sinks,) if isinstance(sinks, Sink) else list(sinks))
    if not out:
        return NULL_TRACER
    return Tracer(out, sync=sync)
