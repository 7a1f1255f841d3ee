"""Open-loop request schedules from a traffic file, and the check's sample.

A serving mix fixes a rate and the laws of prompt and output lengths.
The *set* of requests, their sizes and the gaps between arrivals, is
drawn once from the mix's own ``shape_seed``, so every run of a cell does
the same work (``requests`` arrive over ``requests / rate_per_s``
seconds, which the mix keeps within the window); the run's ``--seed`` only shuffles the order of sizes and
of gaps and draws the prompts' tokens.  Gaps are exponential (Poisson
arrivals); lengths are lognormal, cut to ``[min, max]`` and, for prompts,
rounded up to a multiple of ``step``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def _lengths(rng, law: Dict[str, Any], n: int) -> np.ndarray:
    x = rng.lognormal(math.log(law["median"]), law["sigma"], n)
    step = law.get("step", 1)
    x = np.ceil(x / step) * step
    return np.clip(x, law["min"], law["max"]).astype(int)


def prompt_lengths(mix: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can send."""
    law = mix["prompt"]
    return list(range(law["min"], law["max"] + 1, law.get("step", 1)))


def schedule(mix: Dict[str, Any], seed: int, vocab: int) -> List[Dict]:
    """Requests in order of arrival: ``due_s`` (from the window's start),
    ``prompt`` (token ids) and ``max_new``."""
    n = mix["requests"]
    base = np.random.default_rng(mix["shape_seed"])
    gaps = base.exponential(1.0, n)
    gaps *= n / mix["rate_per_s"] / gaps.sum()     # the mean rate, exactly
    prompts = _lengths(base, mix["prompt"], n)
    outputs = _lengths(base, mix["output"], n)
    rng = np.random.default_rng(seed)
    gaps, prompts, outputs = (rng.permutation(gaps), rng.permutation(prompts),
                              rng.permutation(outputs))
    due = np.cumsum(gaps) - gaps[0]
    return [{"due_s": float(d), "max_new": int(o),
             "prompt": rng.integers(0, vocab, int(p)).tolist()}
            for d, p, o in zip(due, prompts, outputs)]


def sample(done: List[Any], tokens: int, seed: int) -> List[Any]:
    """The finished request with the most tokens, then others drawn from
    the seed, until the sample holds at least ``tokens`` served tokens."""
    if not done:
        return []
    size = lambda r: len(r.prompt) + len(r.output)
    first = max(done, key=size)
    rest = [r for r in done if r is not first]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, served = [first], len(first.output)
    for k in order:
        if served >= tokens:
            break
        out.append(rest[k])
        served += len(rest[k].output)
    return out
