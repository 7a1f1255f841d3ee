"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's
root.  Its configuration is ``bench/configs/<config>.json``, whose
``model_type`` names the architecture module ``bench/archs/<model_type>.py``,
its traffic ``bench/traffic/<traffic>.json``, whose ``kind`` names the
module of ``bench/kinds/`` that runs it, and its limits
``bench/limits/<workload>.json``.
Each metric is read by ``bench/metrics/<metric>.py``.  Adding a cell, a
metric or an architecture adds such files and entries, and edits none,
as long as the program trains or serves the architecture through the
entry points a kind drives (``Trainer``, ``ServeEngine``); a new kind of
traffic is new code in ``bench/kinds/``.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from spans, counters and a
profiler trace of the window.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.common import (  # noqa: E402
    BENCH_DIR, CHECKOUT, BenchError, Spans, device_facts, import_program,
    peak_bytes, use_compile_cache,
)
from bench import model  # noqa: E402


def load_spec(root: str = CHECKOUT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_workload(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def load_json(data_dir: str, *parts: str) -> Dict[str, Any]:
    with open(os.path.join(data_dir, *parts)) as f:
        return json.load(f)


def metrics_for(spec: Dict[str, Any], workload: str, traced: bool,
                ) -> List[Dict[str, Any]]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics,
    or with a trace its per-layer ones."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every limited number is present and within its limit."""
    return all(k in numbers and numbers[k] <= v for k, v in limits.items())


def measure(workload: str, seed: int, seconds: float, traced: bool, *,
            spec: Optional[Dict[str, Any]] = None, need_tpu: bool = True,
            overrides: Optional[Dict[str, Dict[str, Any]]] = None,
            controls: bool = False, data_dir: str = BENCH_DIR,
            ) -> Dict[str, Any]:
    """One run of a cell: set-up, window, check.  Returns the result line
    as a dict.  ``overrides`` replace keys of the configuration and the
    traffic file (the CPU rehearsals use them to shrink a cell);
    ``controls`` adds the control's readings (``bench/calibrate.py``).
    Configurations, traffic and limits are looked up by name under
    ``data_dir``, metric readers under ``bench/metrics``."""
    spec = spec or load_spec()
    w = find_workload(spec, workload)
    dev = device_facts()
    if need_tpu and dev["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {dev['platform']}")
    if dev["count"] < w["chips"]:
        raise BenchError(f"{w['chips']} chips asked, {dev['count']} found")
    overrides = overrides or {}
    cfg = model.check_config(load_json(data_dir, "configs",
                                       f"{w['config']}.json"))
    cfg.update(overrides.get("config", {}))
    mix = load_json(data_dir, "traffic", f"{w['traffic']}.json")
    mix.update(overrides.get("traffic", {}))
    limits = load_json(data_dir, "limits", f"{workload}.json")["limits"]
    kind = importlib.import_module(f"bench.kinds.{mix['kind']}")
    import_program()

    spans = Spans(annotate=traced)
    workdir = tempfile.mkdtemp(prefix="bench_")
    try:
        cell = kind.Cell(workload, cfg, mix, seed, spans, workdir,
                         w["chips"])
        cell.setup()
        setup_s = time.perf_counter() - T_START
        for name, t0, t1 in spans.records:
            if name.startswith("setup:"):
                print(f"{name} {t1 - t0:.3f} s", file=sys.stderr)
        print(f"setup:total {setup_s:.3f} s", file=sys.stderr, flush=True)
        rec = cell.window(seconds, traced)
        if traced and not (rec.get("trace") or {}).get("busy_s"):
            raise BenchError("the traced window holds no device op: the "
                             "trace's planes or lines are not as expected")
        mem = peak_bytes()
        numbers = cell.check(controls)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    rec.update(setup_s=setup_s, device_kind=dev["kind"],
               platform=dev["platform"])
    metrics = {}
    for m in metrics_for(spec, workload, traced):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=mem)
    out: Dict[str, Any] = {
        "correct": judge(numbers, limits) and rec["failed"] == 0,
        "attempted": rec["attempted"], "failed": rec["failed"],
        "metrics": metrics, "device": device,
    }
    if traced:
        t = rec["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = {"device_ops": t.get("device_ops", []),
                            "idle_gaps": t.get("idle_gaps", [])}
    out["checks"] = {k: {"value": numbers.get(k), "limit": v}
                     for k, v in limits.items()}
    out["_info"] = {k: v for k, v in numbers.items() if k not in limits}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    try:
        out = measure(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    info = out.pop("_info")
    for k, v in info.items():
        print(f"info {k} = {v}", file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"check correct = {out['correct']}", file=sys.stderr, flush=True)
    try:
        with open("/proc/self/io") as f:
            io = dict(line.split(": ") for line in f.read().splitlines())
        print(f"bytes written = {int(io['wchar'])}", file=sys.stderr,
              flush=True)
    except (OSError, KeyError, ValueError):
        pass
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
