"""``repro.obs`` — structured telemetry for the round runtime.

Tracing whose spans also land on the profiler's clock
(:mod:`repro.obs.trace`), the clock-model ledger quantifying how well the
Problem-2 cost model tracks execution (:mod:`repro.obs.ledger`), one
shared formatting path for verbose output (:mod:`repro.obs.format`), and
a terminal timeline renderer
(``python -m repro.obs.timeline events.jsonl``).

Instrumented producers take a single ``tracer=`` hook (default
:data:`NULL_TRACER`, zero overhead): :class:`repro.fl.runtime.RoundRuntime`
(and its ``run_federated`` / ``run_fleet`` / ``launch.train`` front-ends)
emits phase spans, counters, and one ledger event per executed round; the
:mod:`repro.fl.backends` execution backends emit ``round_step`` (fused) or
``local_train`` / ``aggregate`` spans and bytes-aggregated counters. Every
span, traced or not, is also a ``repro.<phase>`` profiler annotation
(:func:`annotate`).
"""
from repro.obs.format import format_eval, format_replan
from repro.obs.ledger import (drift_summary, expected_depth, ledger_rows,
                              phase_table, round_record)
from repro.obs.trace import (NULL_TRACER, PHASES, JsonlSink, MemorySink,
                             NullTracer, Sink, Span, Tracer, annotate,
                             annotated, make_tracer, now)

__all__ = [
    "now", "PHASES", "annotate", "annotated", "Sink", "MemorySink",
    "JsonlSink", "Span", "Tracer", "NullTracer", "NULL_TRACER", "make_tracer",
    "round_record", "ledger_rows", "phase_table", "drift_summary",
    "expected_depth", "format_eval", "format_replan",
]
