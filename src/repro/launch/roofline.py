"""Roofline term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all per-chip per-step:

  compute   = HLO_FLOPs / peak_FLOPs            (cost_analysis 'flops')
  memory    = HLO_bytes / HBM_bw                (cost_analysis 'bytes accessed')
  collective= collective_bytes / ICI_bw         (parsed from optimized HLO)

cost_analysis on an SPMD executable reports the PER-DEVICE program (we
verified: a 2-way-sharded matmul reports half the dense FLOPs), so no
chip division is applied. Collective bytes are summed over every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
in ``compiled.as_text()`` with ring-model wire factors; ops inside while
bodies are counted once (HLO cost analysis does the same for FLOPs — the
terms are per *relaxation round* for the Steiner cells).

Hardware constants (TPU v5e): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI (one link per mesh neighbor; conservative single-link model).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

# e.g. "bf16[16,1024]{1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# wire bytes per device ≈ factor × result bytes (ring model, n→∞ limit)
_WIRE_FACTOR = {
    "all-gather": 1.0,  # receives (n-1)/n of the gathered result
    "all-reduce": 2.0,  # reduce-scatter + all-gather
    "reduce-scatter": 1.0,  # sends (n-1)/n of the input (≈ n× result)
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _line_bytes(line: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(line):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Wire bytes per device, by collective kind, from optimized HLO."""
    out = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s or "=" not in s:
            continue
        for kind in _COLLECTIVES:
            # match the opcode position: "... = TYPE... kind(" — exclude
            # -start/-done pairs double counting (count only -start or bare)
            if f" {kind}(" in s or f" {kind}-start(" in s:
                # result bytes: shapes on the LHS of the op name
                lhs = s.split(f" {kind}")[0]
                out[kind] += _line_bytes(lhs) * _WIRE_FACTOR[kind]
                break
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_hbm: float
    bytes_wire: float
    coll_breakdown: Dict[str, float]
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    model_flops_per_chip: Optional[float] = None
    useful_ratio: Optional[float] = None

    def row(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_hbm": self.bytes_hbm,
            "bytes_wire": self.bytes_wire,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "model_flops_per_chip": self.model_flops_per_chip,
            "useful_ratio": self.useful_ratio,
            **{f"coll_{k}": v for k, v in self.coll_breakdown.items()},
        }


def analyze(compiled, model_flops_total: Optional[float] = None,
            n_chips: int = 256) -> Roofline:
    """Builds the three-term roofline from a compiled executable."""
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    bts = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    return analyze_terms(flops, bts, coll, model_flops_total, n_chips)


def analyze_terms(flops: float, bts: float, coll: Dict[str, float],
                  model_flops_total: Optional[float] = None,
                  n_chips: int = 256) -> Roofline:
    """Roofline from explicit (flops, bytes, collective) per-device terms."""
    wire = sum(coll.values())
    t_c = flops / PEAK_FLOPS
    t_m = bts / HBM_BW
    t_x = wire / ICI_BW
    dominant = max(
        (("compute", t_c), ("memory", t_m), ("collective", t_x)),
        key=lambda kv: kv[1],
    )[0]
    mf = model_flops_total / n_chips if model_flops_total else None
    return Roofline(
        flops=flops,
        bytes_hbm=bts,
        bytes_wire=wire,
        coll_breakdown=coll,
        t_compute=t_c,
        t_memory=t_m,
        t_collective=t_x,
        dominant=dominant,
        model_flops_per_chip=mf,
        useful_ratio=(mf / flops) if (mf and flops) else None,
    )


def memory_report(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "argument_gb": m.argument_size_in_bytes / 2**30,
        "output_gb": m.output_size_in_bytes / 2**30,
        "temp_gb": m.temp_size_in_bytes / 2**30,
        "alias_gb": m.alias_size_in_bytes / 2**30,
        "peak_est_gb": (
            m.argument_size_in_bytes
            + m.output_size_in_bytes
            + m.temp_size_in_bytes
            - m.alias_size_in_bytes
        )
        / 2**30,
        "fits_16gb": (
            m.argument_size_in_bytes
            + m.output_size_in_bytes
            + m.temp_size_in_bytes
            - m.alias_size_in_bytes
        )
        < 16 * 2**30,
    }
