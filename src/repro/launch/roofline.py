"""Roofline-term derivation from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / peak            (cost_analysis, per device)
    memory term     = HLO_bytes / HBM_bw
    collective term = collective_bytes / link_bw  (parsed from HLO text)

The peaks come from :data:`PEAKS`, keyed by ``device_kind`` as JAX reports
it; a kind that is not in the table is an error, never a default.  The
dominant term is the structural bottleneck the §Perf loop iterates on.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

#: Published per-chip peaks (Google Cloud documentation, "TPU v5e"):
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
#: interconnect over four links (50 GB/s each).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32"
                       r"|s64|u64|c64)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*|\S+\s+)?(all-gather|all-reduce|reduce-scatter"
    r"|all-to-all|collective-permute)(?:-start|-done)?\(")


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in (post-SPMD) HLO text."""
    out: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith(("//", "#")) or "= " not in s:
            continue
        m = _OP_RE.search(s)
        if m is None:
            continue
        if "-done(" in s:
            continue   # async completion carries no new bytes
        kind = m.group(1)
        paren = s[m.end() - 1:]
        shapes = _SHAPE_RE.findall(paren)
        if not shapes:
            # compiled HLO prints operands bare: use the result shape
            shapes = _SHAPE_RE.findall(s)[:1]
        out[kind] += sum(_shape_bytes(d, dims) for d, dims in shapes)
        out["count"] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   coll_bytes_per_device: float, *, device_kind: str,
                   ) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    peak = PEAKS[device_kind]
    t_c = flops_per_device / peak["flops"]
    t_m = bytes_per_device / peak["hbm_bw"]
    t_x = coll_bytes_per_device / peak["ici_bw"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    total = max(t_c, t_m, t_x)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "step_lower_bound_s": total,
        "roofline_fraction_compute": t_c / total if total > 0 else 0.0,
    }


def model_flops(n_params_active: int, tokens: int, *, train: bool) -> float:
    """6·N·D for training (fwd+bwd), 2·N·D forward-only."""
    return (6.0 if train else 2.0) * n_params_active * tokens
