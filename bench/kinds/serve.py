"""Serving cells: ``ServeEngine.step`` under an open-loop arrival schedule.

Set-up makes the weights from the seed, builds the engine and warms every
prompt length the mix can send (prefill compiles per length) and the
decode step.  The window adds each request to the engine once it is due
and calls ``step()``; it never waits for the engine, only for the next
arrival when the engine is idle.  After ``--seconds`` no more requests
are added, and the engine runs on until every request that was due has
finished (at most ``drain_s``), so that each has its first token.  Token
times are host-clock readings after the ``step()`` that produced them.
The check runs the plain reference over a sample of the finished
requests and reads, for each served token, how far its reference logit
lies below the reference's best at that position; a sample short of
``check_tokens`` served tokens is a failure of its own.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import arrivals, flops, model, reference, trace
from bench.common import Spans

SPANS = ("tick", "wait")


class Cell:
    def __init__(self, name: str, cfg: Dict[str, Any], mix: Dict[str, Any],
                 seed: int, spans: Spans, workdir: str, chips: int):
        self.name, self.cfg, self.mix, self.seed = name, cfg, mix, seed
        self.spans, self.chips = spans, chips

    def setup(self) -> None:
        from repro.serve.engine import Request, ServeEngine

        self.Request = Request
        self.pcfg = model.program_config(self.cfg)
        self.engine = ServeEngine(
            self.pcfg, model.make_weights(self.cfg, self.seed),
            slots=self.mix["slots"], max_len=self.mix["max_len"],
            seed=self.seed % 2 ** 31)
        self.schedule = arrivals.schedule(self.mix, self.seed,
                                          self.cfg["vocab_size"])
        # warm every prompt length of the mix, and the decode step
        for k, n in enumerate(arrivals.prompt_lengths(self.mix)):
            self.engine.add_request(Request(rid=-1 - k, prompt=[1] * n,
                                            max_new_tokens=2))
        self.engine.run_until_done()

    # ---- the timed window ----------------------------------------------------
    def window(self, seconds: float, traced: bool) -> Dict[str, Any]:
        eng, sched = self.engine, self.schedule
        reqs: List[Any] = []
        times: List[List[float]] = []
        open_: List[int] = []
        ticks: List[tuple] = []      # (start, end, admitted, decoded)
        lateness: List[float] = []
        ev: Dict[str, Any] = {}
        tr = self.mix["trace_seconds"]
        t0 = time.perf_counter()
        deadline = t0 + seconds
        drain_end = deadline + self.mix["drain_s"]
        tracing = False
        i = 0
        while True:
            now = time.perf_counter()
            if traced and not tracing and now - t0 >= self.mix["trace_from_s"]:
                tracing = True
                trace_ctx = trace.record(ev)
                trace_ctx.__enter__()
                win_span = self.spans.span("window")
                win_span.__enter__()
            if tracing and now - t0 >= self.mix["trace_from_s"] + tr:
                win_span.__exit__(None, None, None)
                trace_ctx.__exit__(None, None, None)
                tracing, traced = False, False
            if now >= drain_end:
                break
            while i < len(sched) and now < deadline and \
                    t0 + sched[i]["due_s"] <= now:
                r = self.Request(rid=i, prompt=sched[i]["prompt"],
                                 max_new_tokens=sched[i]["max_new"])
                eng.add_request(r)
                reqs.append(r)
                times.append([])
                open_.append(i)
                lateness.append(now - t0 - sched[i]["due_s"])
                i += 1
            if not open_:
                if now >= deadline:
                    break
                nxt = t0 + sched[i]["due_s"] if i < len(sched) else deadline
                with self.spans.span("wait"):
                    time.sleep(max(0.0, min(nxt, deadline) - now))
                continue
            with self.spans.span("tick"):
                ts = time.perf_counter()
                eng.step()
                te = time.perf_counter()
            admitted = decoded = 0
            still = []
            for rid in open_:
                r = reqs[rid]
                new = len(r.output) - len(times[rid])
                if new and not times[rid]:
                    admitted += 1
                decoded += new
                times[rid].extend([te] * new)
                if not r.done:
                    still.append(rid)
            open_ = still
            ticks.append((ts, te, admitted, decoded))
        self.stopped = time.perf_counter()
        if tracing:
            win_span.__exit__(None, None, None)
            trace_ctx.__exit__(None, None, None)
        self.reqs, self.times, self.t0 = reqs, times, t0
        return self._record(t0, deadline, reqs, times, ticks, lateness, ev)

    def _record(self, t0, deadline, reqs, times, ticks, lateness, ev):
        sched, cfg = self.schedule, self.cfg
        ttft, itl = [], []
        pre_f = dec_f = 0.0
        for r, ts in zip(reqs, times):
            due = t0 + sched[r.rid]["due_s"]
            # a request never served counts with all the time it waited
            ttft.append(((ts[0] if ts else self.stopped) - due) * 1e3)
            P = len(r.prompt)
            for k, t in enumerate(ts):
                if t > deadline:
                    break
                if k == 0:
                    pre_f += flops.prefill(cfg, P)
                else:
                    itl.append((t - ts[k - 1]) * 1e3)
                    dec_f += flops.decode_token(cfg, P + k - 1)
        in_win = [t for t in ticks if t[1] <= deadline]
        rec = {
            "window_s": deadline - t0,
            "attempted": len(reqs),
            "failed": sum(1 for r in reqs if not r.done),
            "ttft_ms": ttft, "itl_ms": itl,
            "ticks": [(te - ts, a, d) for ts, te, a, d in in_win],
            "flops": pre_f + dec_f,
            "lateness_s": lateness,
            "chips": self.chips,
        }
        if ev.get("events"):
            lo, hi = trace.window_bounds(ev["events"], "window")
            rec["trace"] = trace.summarize(ev["events"], lo, hi, SPANS,
                                           step_match="")
        return rec

    # ---- the check -------------------------------------------------------------
    def check(self, controls: bool = False) -> Dict[str, Any]:
        eng = self.engine
        eng.cache = eng.params = None
        self.engine = None
        gc.collect()
        done = [r for r in self.reqs if r.done]
        sample = arrivals.sample(done, self.mix["check_tokens"], self.seed)
        top, layers = reference.split_layers(self.cfg, model.flat_leaves(
            model.make_weights(self.cfg, self.seed), self.cfg))
        ref = reference.Reference(self.cfg, "f32")
        ctl = reference.Reference(self.cfg, "fp8") if controls else None
        worst = worst_ctl = 0.0
        L = self.mix["max_len"]
        for r in sample:
            seq = list(r.prompt) + list(r.output[:-1])
            rows = np.arange(len(r.prompt) - 1, len(seq))
            pad = np.zeros(L, np.int32)
            pad[:len(seq)] = seq
            logits = reference.sequence_logits(ref, top, layers, pad)
            worst = max(worst, float(_gap(logits, rows, np.asarray(
                r.output, np.int32))))
            if ctl is not None:
                lc = reference.sequence_logits(ctl, top, layers, pad)
                pick = jnp.argmax(lc[rows], -1)
                worst_ctl = max(worst_ctl, float(_gap(logits, rows, pick)))
        checked = sum(len(r.output) for r in sample)
        out = {"logit_gap": worst, "checked_tokens": checked,
               "check_shortfall": max(0, self.mix["check_tokens"] - checked)}
        if controls:
            out["logit_gap.fp8"] = worst_ctl
        return out


@jax.jit
def _gap(logits, rows, tokens):
    """Largest (best reference logit - reference logit of the token) over
    the rows that produced ``tokens``."""
    sel = logits[rows]
    got = jnp.take_along_axis(sel, tokens[:, None], -1)[:, 0]
    return jnp.max(jnp.max(sel, -1) - got)
