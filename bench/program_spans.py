"""The program's own spans, counter and model scopes in a profiler trace.

The program (``repro.obs``) opens host spans named ``layer.what`` at its
layer boundaries, counts the bytes a checkpoint pulls off the device
(``ckpt.bytes``), and names its model scopes (``attention``, ``mlp``,
``head``, ``optimizer``) with ``jax.named_scope``, which the device ops
carry in their name stack.  This module turns a trace of a window into
per-layer numbers:

* :func:`span_seconds`, :func:`self_seconds`: host spans in the window;
* :func:`scope_seconds`: device time of the leaf ops under one scope;
* :func:`readings`: a training window's numbers, per save and per step.

Run as a script it records one training cell's window and prints these
numbers, the device time of each scope and the idle gaps by the innermost
span (the benchmark's and the program's) as one JSON line:

    python bench/program_spans.py --workload <cell> --seed <n> --seconds 45

Events are :mod:`bench.trace`'s ``(plane, line, name, start_ns, end_ns)``;
the name stacks come beside them, keyed by the op's name.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
from typing import Dict, Iterator, List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import trace  # noqa: E402
from bench.trace import Event  # noqa: E402

#: The program's host spans (``repro.obs.span``), by layer.
PROGRAM_SPANS = (
    "ckpt.save", "ckpt.pull", "ckpt.encode",
    "xufs.write", "xufs.close", "xufs.cache_store",
    "wal.append", "wal.flush",
    "pipeline.read", "pipeline.to_device",
    "train.dispatch", "train.loss_sync",
)
#: The model's named scopes.
SCOPES = ("attention", "mlp", "head", "optimizer")
#: Ops that hold other ops: their time is their body's, counted there.
CONTAINERS = ("while", "call", "conditional")
#: The trace stat that holds an XLA op's name stack (its ``op_name``).
STACK_STAT = "tf_op"


# ---- loading ----------------------------------------------------------------

@contextlib.contextmanager
def record(out: Dict) -> Iterator[None]:
    """Trace the body; on exit ``out["events"]`` holds its events and
    ``out["stacks"]`` the name stack of each device op (:func:`load`)."""
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
        out["events"], out["stacks"] = load(files[0]) if files else ([], {})
    finally:
        shutil.rmtree(d, ignore_errors=True)


def load(path: str):
    """The events of an ``.xplane.pb`` (as :func:`bench.trace.load_events`
    gives them) and the device ops' name stacks (:func:`name_stacks`)."""
    return trace.load_events(path), name_stacks(path)


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator:
    """``(field number, value)`` of a protobuf message: an int for a
    varint, the bytes for every other wire type."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def name_stacks(path: str) -> Dict[str, str]:
    """``{op name: name stack}`` of the device planes' ops.  A TPU trace
    keeps the stack as the stat :data:`STACK_STAT` of the op's event
    metadata, which ``ProfileData`` does not show, so it is read from the
    wire format of XSpace: planes 1; XPlane name 2, event_metadata 4,
    stat_metadata 5 (map entries: key 1, value 2); XEventMetadata name 2,
    stats 5; XStatMetadata id 1, name 2; XStat metadata_id 1, str_value 5."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    stacks: Dict[str, str] = {}
    for fno, plane in _fields(buf):
        if fno != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in fields if f == 2), "")
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        metas = [dict(_fields(dict(_fields(v)).get(2, b"")))
                 for f, v in fields if f == 5]
        ids = {m.get(1, 0) for m in metas
               if bytes(m.get(2, b"")).decode() == STACK_STAT}
        for f, entry in fields:
            if f != 4:
                continue
            op, stack = "", ""
            for g, v in _fields(dict(_fields(entry)).get(2, b"")):
                if g == 2:
                    op = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if st.get(1, 0) in ids and 5 in st:
                        stack = bytes(st[5]).decode()
            if stack:
                stacks[op] = stack
    return stacks


# ---- host spans -------------------------------------------------------------

def _spans(events: Sequence[Event], lo: float, hi: float, name: str,
           within: Optional[str] = None) -> List[Event]:
    """Host spans ``name`` inside [lo, hi], and, with ``within``, inside a
    span of that name on the same thread."""
    own = [e for e in events if e[0] == trace.HOST_PLANE and e[2] == name
           and e[3] >= lo and e[4] <= hi]
    if within is None:
        return own
    outer = [e for e in events
             if e[0] == trace.HOST_PLANE and e[2] == within]
    return [e for e in own if any(o[1] == e[1] and o[3] <= e[3]
                                  and e[4] <= o[4] for o in outer)]


def span_seconds(events: Sequence[Event], lo: float, hi: float, name: str,
                 within: Optional[str] = None) -> List[float]:
    """Durations (s) of the host spans ``name`` in the window [lo, hi] (ns),
    optionally only those nested in a span ``within``."""
    return [(e[4] - e[3]) / 1e9 for e in _spans(events, lo, hi, name,
                                                within)]


def self_seconds(events: Sequence[Event], lo: float, hi: float, name: str,
                 within: Optional[str] = None) -> List[float]:
    """As :func:`span_seconds`, less the time of the program's spans
    nested in each (its children)."""
    kids = [e for e in events if e[0] == trace.HOST_PLANE
            and e[2] in PROGRAM_SPANS and e[2] != name]
    out = []
    for _, line, _, s, e in _spans(events, lo, hi, name, within):
        inner = trace.union((k[3], k[4]) for k in kids if k[1] == line
                            and s <= k[3] and k[4] <= e)
        out.append((e - s - trace.length(inner)) / 1e9)
    return out


# ---- device scopes ----------------------------------------------------------

def _scopes(stack: str) -> List[str]:
    """The path segments of a name stack, with JAX's transform wrappers
    such as ``transpose(jvp(attention))`` seen through."""
    out = []
    for seg in stack.split("/"):
        while True:
            m = re.fullmatch(r"[\w.-]+\((.*)\)", seg)
            if not m:
                break
            seg = m.group(1)
        out.append(seg)
    return out


def is_container(name: str) -> bool:
    """Whether the op (HLO text ``%name = type opcode(...)``, or a bare
    instruction name) is a ``while``, ``call`` or ``conditional``."""
    head = name.split("=", 1)
    if len(head) == 2:
        text = re.sub(r"\{[^{}]*\}", "", head[1])      # layouts
        m = re.search(r"[\s)](\w[\w-]*)\(", " " + text)
        return bool(m) and m.group(1) in CONTAINERS
    return name.lstrip("%").split(".")[0] in CONTAINERS


def scope_seconds(events: Sequence[Event], plane: str, scope: str,
                  stacks: Dict[str, str], lo: float = float("-inf"),
                  hi: float = float("inf")) -> float:
    """Seconds of the union of ``plane``'s leaf ops in [lo, hi] whose name
    stack has ``scope`` as a path segment.  A fusion counts under the
    stack the trace gives it (its root op's)."""
    cache: Dict[str, bool] = {}

    def under(name: str) -> bool:
        if name not in cache:
            cache[name] = (not is_container(name)
                           and scope in _scopes(stacks.get(name, "")))
        return cache[name]

    iv = [(s, e) for _, _, n, s, e in trace.op_events(events, plane)
          if under(n)]
    return trace.length(trace.clip(trace.union(iv), lo, hi)) / 1e9


# ---- a window's numbers -----------------------------------------------------

def readings(events: Sequence[Event], stacks: Dict[str, str], lo: float,
             hi: float, ckpt_bytes: Optional[int] = None,
             step_match: str = "train_step") -> Dict[str, Optional[float]]:
    """The per-layer numbers of a training window [lo, hi] (ns).  Each is
    ``None`` where its spans or scope are absent from the trace.

    Per save (``ckpt.save`` spans): seconds pulling leaves off the device,
    encoding them, buffering them in the file (``xufs.write`` and the
    self time of ``xufs.close``), storing them in the cache and appending
    them to the WAL; the pull's GB/s from ``ckpt_bytes``.  Per step
    (``train.dispatch`` spans): ms reading shards, ms moving the batch to
    the device.  Per run of the step program: device ms of each scope."""
    def total(name, within=None, own=False):
        f = self_seconds if own else span_seconds
        xs = f(events, lo, hi, name, within)
        return sum(xs) if xs else None

    out: Dict[str, Optional[float]] = {}
    saves = len(_spans(events, lo, hi, "ckpt.save"))
    for key, name in (("ckpt_pull_s", "ckpt.pull"),
                      ("ckpt_encode_s", "ckpt.encode"),
                      ("ckpt_cache_store_s", "xufs.cache_store"),
                      ("ckpt_wal_append_s", "wal.append")):
        t = total(name, "ckpt.save")
        out[key] = t / saves if saves and t is not None else None
    parts = [total("xufs.write", "ckpt.save"),
             total("xufs.close", "ckpt.save", own=True)]
    out["ckpt_buffer_s"] = (sum(p for p in parts if p is not None) / saves
                            if saves and parts != [None, None] else None)
    pull = total("ckpt.pull", "ckpt.save")
    out["ckpt_pull_gb_per_s"] = (ckpt_bytes / pull / 1e9
                                 if pull and ckpt_bytes else None)
    steps = len(_spans(events, lo, hi, "train.dispatch"))
    for key, name in (("input_read_ms", "pipeline.read"),
                      ("input_h2d_ms", "pipeline.to_device")):
        t = total(name)
        out[key] = 1e3 * t / steps if steps and t is not None else None
    planes = trace.device_planes(events)
    runs = [s for p, line, n, s, _ in events if planes and p == planes[0]
            and line == trace.MODULES_LINE and step_match in n
            and lo <= s <= hi]
    for scope in SCOPES:
        t = (scope_seconds(events, planes[0], scope, stacks, lo, hi)
             if runs else 0.0)
        out[f"{scope}_device_ms"] = 1e3 * t / len(runs) if t > 0 else None
    return out


# ---- one cell's window, traced ----------------------------------------------

def measure(workload: str, seed: int, seconds: float, *,
            need_tpu: bool = True, spec: Optional[Dict] = None,
            overrides: Optional[Dict[str, Dict]] = None) -> Dict:
    """Set up a training cell as ``bench/run.py`` does, trace its window,
    and return the window's numbers (:func:`readings`), the device time of
    the step, and the idle gaps by the innermost span.  ``overrides``
    shrink the cell as in :func:`bench.run.measure`."""
    from bench import model, run
    from bench.common import (BenchError, Spans, device_facts,
                              import_program)
    from bench.kinds import train

    spec = spec or run.load_spec()
    w = run.find_workload(spec, workload)
    if need_tpu and device_facts()["platform"] != "tpu":
        raise BenchError("no TPU")
    overrides = overrides or {}
    cfg = model.check_config(run.load_json(run.BENCH_DIR, "configs",
                                           f"{w['config']}.json"))
    cfg.update(overrides.get("config", {}))
    mix = run.load_json(run.BENCH_DIR, "traffic", f"{w['traffic']}.json")
    mix.update(overrides.get("traffic", {}))
    if mix["kind"] != "train":
        raise BenchError("only training cells run the program's spans")
    import_program()
    from repro import obs

    workdir = tempfile.mkdtemp(prefix="bench_")
    ev: Dict = {}
    try:
        cell = train.Cell(workload, cfg, mix, seed, Spans(annotate=True),
                          workdir, w["chips"])
        cell.setup()
        b0 = obs.counts.get("ckpt.bytes", 0)
        with record(ev):
            rec = cell.window(seconds, False)
        ckpt_bytes = obs.counts.get("ckpt.bytes", 0) - b0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    events, stacks = ev["events"], ev["stacks"]
    lo, hi = trace.window_bounds(events, "window")
    planes = trace.device_planes(events)
    step_s = (trace.module_seconds(events, planes[0], "train_step")
              if planes else [])
    names = train.SPANS + PROGRAM_SPANS
    return {
        "workload": workload, "seed": seed,
        "steps": rec["steps"], "window_s": rec["window_s"],
        "tokens_per_s": rec["tokens"] / rec["window_s"],
        "ckpt_save_s": rec["ckpt_save_s"], "ckpt_bytes": ckpt_bytes,
        "busy_s": trace.busy_seconds(events, lo, hi),
        "trace_window_s": (hi - lo) / 1e9,
        "step_device_ms": 1e3 * statistics.mean(step_s) if step_s else None,
        "readings": readings(events, stacks, lo, hi, ckpt_bytes),
        "idle_gaps": trace.top(trace.idle_by_span(
            events, planes[0], lo, hi, names), len(names) + 1)
        if planes else [],
        "ops_with_stack": len(stacks),
        "unscoped_ops": unscoped(events, planes[0], stacks, lo, hi)
        if planes else [],
    }


def unscoped(events: Sequence[Event], plane: str, stacks: Dict[str, str],
             lo: float, hi: float, n: int = 10) -> List[List]:
    """The ``n`` leaf ops of ``plane`` in [lo, hi] that take the most time
    under none of :data:`SCOPES`: ``[op, seconds, name stack]``."""
    secs: Dict[str, float] = {}
    for _, _, name, s, e in trace.op_events(events, plane):
        if s < lo or e > hi or is_container(name):
            continue
        if not set(SCOPES) & set(_scopes(stacks.get(name, ""))):
            secs[name] = secs.get(name, 0.0) + (e - s) / 1e9
    return [[trace.short(k), v, stacks.get(k, "")]
            for k, v in trace.top(secs, n)]


def main(argv: Optional[List[str]] = None) -> int:
    from bench.common import BenchError, use_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    use_compile_cache()
    try:
        out = measure(args.workload, args.seed, args.seconds)
    except BenchError as e:
        print(f"program_spans: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
