"""From a profiler trace to numbers: device busy time, idle gaps and what
the host did in them, time per device op, per program and in collectives.

:func:`record` wraps a window in ``jax.profiler`` and :func:`load_events`
flattens the ``.xplane.pb`` it writes into plain events
``(plane, line, name, start_ns, end_ns)``.  Everything else works on such
lists, so it can be checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, Iterator, List, Sequence, Tuple

Event = Tuple[str, str, str, float, float]
Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


@contextlib.contextmanager
def record(out: Dict[str, List[Event]]) -> Iterator[None]:
    """Trace the body; on exit ``out["events"]`` holds its events.  The
    Python tracer is off: host spans come from ``TraceAnnotation``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out["events"] = load_events(files[0]) if files else []
    finally:
        shutil.rmtree(d, ignore_errors=True)


def load_events(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith(DEVICE_PREFIX)
                or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                events.append((plane.name, line.name, e.name,
                               float(e.start_ns), float(e.end_ns)))
    return events


# ---- interval arithmetic -----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the intervals ``a`` outside the intervals ``b``."""
    a, b = union(a), union(b)
    out: List[Interval] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def innermost(spans: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """The timeline of the innermost (shortest) span open at each moment,
    as disjoint ``(start, end, name)`` pieces in order."""
    cuts = sorted({t for *_, s, e in spans for t in (s, e)})
    out: List[Tuple[float, float, str]] = []
    by_start = sorted(spans, key=lambda e: e[3])
    open_: List[Event] = []
    i = 0
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][3] <= lo:
            open_.append(by_start[i])
            i += 1
        open_ = [x for x in open_ if x[4] > lo]
        if open_:
            name = min(open_, key=lambda x: x[4] - x[3])[2]
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


# ---- reductions --------------------------------------------------------------

def device_planes(events: Sequence[Event]) -> List[str]:
    """Device planes in the order of their ordinal (device 0 first)."""
    def ordinal(p: str) -> int:
        m = re.match(r"\d+", p[len(DEVICE_PREFIX):])
        return int(m.group()) if m else 0
    return sorted({p for p, *_ in events if p.startswith(DEVICE_PREFIX)},
                  key=ordinal)


def op_events(events: Sequence[Event], plane: str) -> List[Event]:
    return [e for e in events if e[0] == plane and e[1] == OPS_LINE]


def busy(events: Sequence[Event], plane: str, lo: float, hi: float,
         ) -> List[Interval]:
    """Union of the device's op intervals inside [lo, hi]."""
    return clip(union((s, e) for *_, s, e in op_events(events, plane)),
                lo, hi)


def busy_seconds(events: Sequence[Event], lo: float, hi: float) -> float:
    """Busy seconds in [lo, hi], averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(length(busy(events, p, lo, hi)) for p in planes) \
        / len(planes) / 1e9


def op_seconds(events: Sequence[Event], plane: str) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for _, _, name, s, e in op_events(events, plane):
        out[name] += (e - s) / 1e9
    return dict(out)


def module_seconds(events: Sequence[Event], plane: str, match: str,
                   ) -> List[float]:
    """Durations of the runs of each program whose name contains
    ``match`` (one per call, from the modules line)."""
    return [(e - s) / 1e9 for p, line, name, s, e in events
            if p == plane and line == MODULES_LINE and match in name]


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


def collective_exposed_seconds(events: Sequence[Event], plane: str) -> float:
    """Time of collective ops during which no other op runs."""
    ops = op_events(events, plane)
    coll = union((s, e) for _, _, n, s, e in ops if is_collective(n))
    comp = [(s, e) for _, _, n, s, e in ops if not is_collective(n)]
    return length(subtract(coll, comp)) / 1e9


def host_spans(events: Sequence[Event], names: Iterable[str]) -> List[Event]:
    wanted = set(names)
    return [e for e in events if e[0] == HOST_PLANE and e[2] in wanted]


def idle_by_span(events: Sequence[Event], plane: str, lo: float, hi: float,
                 span_names: Iterable[str]) -> Dict[str, float]:
    """Seconds of the device's idle time in [lo, hi], by the innermost
    (shortest) host span open at each moment; ``(no span)`` where none
    is."""
    idle = subtract([(lo, hi)], busy(events, plane, lo, hi))
    pieces = innermost(host_spans(events, span_names))
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
            if ov > 0:
                out[pieces[k][2]] += ov / 1e9
                covered += ov
            k += 1
        out["(no span)"] += (e - s - covered) / 1e9
    return {k: v for k, v in out.items() if v > 0}


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def short(name: str, width: int = 120) -> str:
    """An op's HLO text without its layouts, cut to ``width`` letters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def summarize(events: Sequence[Event], lo: float, hi: float,
              span_names: Iterable[str], step_match: str = "") -> Dict:
    """What the metric readers take from a trace of the window [lo, hi]
    (nanoseconds on the trace's clock)."""
    planes = device_planes(events)
    if not planes:
        return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9}
    p0 = planes[0]
    return {
        "busy_s": busy_seconds(events, lo, hi),
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[short(k), v] for k, v in
                       top(op_seconds(events, p0))],
        "idle_gaps": top(idle_by_span(events, p0, lo, hi, span_names)),
        "step_s": module_seconds(events, p0, step_match)
        if step_match else [],
        "collective_exposed_s": collective_exposed_seconds(events, p0),
    }


def window_bounds(events: Sequence[Event], name: str) -> Tuple[float, float]:
    """Start and end (ns) of the host span that marks the traced window."""
    for p, _, n, s, e in events:
        if p == HOST_PLANE and n == name:
            return s, e
    raise LookupError(f"no host span {name!r} in the trace")


def idle_percent(summary) -> "float | None":
    """100 * (1 - busy / window) of a :func:`summarize` result; ``None``
    where there is no trace or no device op in it."""
    if not summary or summary.get("busy_s", 0.0) <= 0.0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
