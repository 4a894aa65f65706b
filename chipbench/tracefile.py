"""Reduce a JAX profiler trace to the intervals the per-layer metrics read.

``load(path)`` turns an ``.xplane.pb`` into a plain dict, the form that a
recorded trace is kept in for the self-checks:

* ``host``: the benchmark's own spans (``chipbench.*`` TraceAnnotations)
  as ``[name, start_ns, dur_ns, {stat: value}]``;
* ``devices``: per device plane (``/device:TPU:n``), its ``modules`` (one
  event per executed program) and ``ops`` (one per operation of the
  ``XLA Ops`` line, nested: a loop's event holds its body's), each as
  ``[name, start_ns, dur_ns]`` with the op's short HLO name (``%fusion.3``,
  ``%adel_agg_q8.12``), ``async``: the ``Async XLA Ops`` line, where an
  asynchronous copy or collective spans its start to its done, and
  ``collectives``: the opcode of each op name that is a collective.

Host and device events share the profiler's clock.
"""
from __future__ import annotations

import glob
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the opcode of a collective in an op's instruction text (its name, such as
# ``%psum.127``, comes from the JAX op and says nothing of what it is)
COLLECTIVE = re.compile(r" (all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(?:-start|-done)?\(")


def _line_kind(name: str) -> str | None:
    return {"xla modules": "modules", "xla ops": "ops",
            "async xla ops": "async"}.get(name.lower())


def load(log_dir: str) -> dict:
    import jax
    files = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    out = {"host": [], "devices": {}, "lines": {}}
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            out["lines"].setdefault(plane.name, []).append(line.name)
            if dev:
                kind = _line_kind(line.name)
                if kind is None:
                    continue
                d = out["devices"].setdefault(
                    plane.name, {"modules": [], "ops": [], "async": [],
                                 "collectives": {}})
                for e in line.events:
                    name = e.name.split(" = ", 1)[0]
                    d[kind].append([name, e.start_ns, e.duration_ns])
                    if kind != "modules" and name not in d["collectives"]:
                        m = COLLECTIVE.search(e.name)
                        if m:
                            d["collectives"][name] = m.group(1)
            elif plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith("chipbench."):
                        out["host"].append([e.name, e.start_ns, e.duration_ns,
                                            {k: v for k, v in e.stats}])
    return out


def spans(tr: dict, name: str) -> list:
    """Host spans called ``chipbench.<name>``, in start order."""
    return sorted((s for s in tr["host"] if s[0] == f"chipbench.{name}"),
                  key=lambda s: s[1])


def window(tr: dict) -> tuple[float, float]:
    (w,) = spans(tr, "window")
    return float(w[1]), float(w[1] + w[2])


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d, *_ in intervals
                if s < hi and s + d > lo)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def leaf_ops(tr: dict, plane: str) -> list:
    """The ops of ``plane`` that hold no other op: the work itself, not the
    loops and calls around it."""
    ev = sorted(tr["devices"][plane]["ops"], key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ev, ev[1:] + [None])
            if nxt is None or nxt[1] >= o[1] + o[2]]


def busy_intervals(tr: dict, plane: str, *, programs: bool = False) -> list:
    """Merged intervals of the window in which an op ran on ``plane`` (leaf
    ops where the trace has ops, else whole programs); ``programs``: in
    which a program was running at all, gaps inside it counted busy."""
    d = tr["devices"][plane]
    lo, hi = window(tr)
    if programs or not d["ops"]:
        return union(d["modules"], lo, hi)
    return union(leaf_ops(tr, plane), lo, hi)


def busy_ns(tr: dict, plane: str) -> float:
    """Nanoseconds of the window in which some operation ran on ``plane``."""
    return float(sum(e - s for s, e in busy_intervals(tr, plane)))


def uncovered_ns(intervals, busy: list) -> float:
    """Nanoseconds of ``intervals`` ([start, end) pairs) outside the merged
    ``busy`` intervals."""
    tot = 0.0
    for s0, e0 in intervals:
        cov = sum(max(0.0, min(e, e0) - max(s, s0)) for s, e in busy
                  if s < e0 and e > s0)
        tot += (e0 - s0) - cov
    return tot


def idle_within(tr: dict, plane: str, spans_: list) -> float:
    """Nanoseconds of the host ``spans_`` ([start, end) pairs) in which no
    program ran on ``plane``: the host's time on the critical path (a gap
    inside a running program is the device's own)."""
    return uncovered_ns(spans_, busy_intervals(tr, plane, programs=True))


def idle_gaps(tr: dict, plane: str) -> list:
    """[(start, end)] of the window's stretches with nothing on ``plane``."""
    lo, hi = window(tr)
    gaps, t = [], lo
    for s, e in busy_intervals(tr, plane):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def what_ran(tr: dict, plane: str, t: float) -> str:
    """Names an idle moment of ``plane``: the program it falls inside, or
    else the benchmark span open on the host."""
    for name, s, d in tr["devices"][plane]["modules"]:
        if s <= t < s + d:
            return f"inside {name.split('(')[0]}"
    return open_span(tr, t)


def open_span(tr: dict, t: float) -> str:
    """The innermost benchmark span open on the host at time ``t``."""
    best = None
    for name, s, d, _ in tr["host"]:
        if name != "chipbench.window" and s <= t < s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0].split(".", 1)[1] if best else "host between calls"


def module_totals(tr: dict, plane: str) -> dict:
    """{program name: (count, device ns)} over the window."""
    lo, hi = window(tr)
    out: dict = {}
    for name, s, d in tr["devices"][plane]["modules"]:
        if lo <= s < hi:
            c, t = out.get(name, (0, 0.0))
            out[name] = (c + 1, t + d)
    return out


def op_totals(tr: dict, plane: str, *, leaves: bool = False) -> dict:
    """{op name: device ns} over the window; ``leaves`` keeps only the ops
    that hold no other op."""
    lo, hi = window(tr)
    out: dict = {}
    ops = leaf_ops(tr, plane) if leaves else tr["devices"][plane]["ops"]
    for name, s, d in ops:
        if lo <= s < hi:
            out[name] = out.get(name, 0.0) + d
    return out


def step_program(tr: dict, plane: str, rounds: int) -> str | None:
    """The round-step program: the one run once per round that takes the
    most device time."""
    mods = module_totals(tr, plane)
    cands = [(t, n) for n, (c, t) in mods.items() if c == rounds]
    return max(cands)[1] if cands else None


def planes(tr: dict) -> list:
    return sorted(tr["devices"], key=lambda p: int(DEVICE_PLANE.match(p)[1]))


def mean_over_planes(tr: dict, fn) -> float | None:
    """Mean of ``fn(plane)`` over the device planes; None without any."""
    ps = planes(tr)
    return float(np.mean([fn(p) for p in ps])) if ps else None


def collective_intervals(tr: dict, plane: str, opcode: str) -> list:
    """Merged intervals of the window in which a collective ``opcode``
    (``all-reduce``) was in flight on ``plane``, synchronous or from its
    start to its done."""
    d = tr["devices"][plane]
    lo, hi = window(tr)
    kinds = d.get("collectives", {})
    return union([e for e in d["ops"] + d.get("async", [])
                  if kinds.get(e[0]) == opcode], lo, hi)


def exposed_ns(tr: dict, plane: str, intervals: list, opcode: str) -> float:
    """Nanoseconds of ``intervals`` in which no leaf op other than the
    ``opcode`` collectives ran on ``plane``."""
    lo, hi = window(tr)
    kinds = tr["devices"][plane].get("collectives", {})
    return uncovered_ns(intervals, union(
        [o for o in leaf_ops(tr, plane) if kinds.get(o[0]) != opcode],
        lo, hi))
