"""Three-term roofline analysis from compiled dry-run artifacts.

    compute term    = HLO_FLOPs      / (chips * peak FLOP/s)
    memory term     = HLO_bytes      / (chips * HBM bandwidth)
    collective term = collective_B   / (chips * ICI link bandwidth)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis(); collective bytes
are NOT in cost_analysis, so we parse the (optimized) HLO text and sum the
result-shape sizes of every collective op (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional

# TPU v5e constants (per chip).
PEAK_BF16 = 197e12          # FLOP/s
PEAK_INT8 = 394e12          # OP/s
HBM_BW = 819e9              # B/s
ICI_BW = 50e9               # B/s per link

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

# e.g.  "bf16[256,4096,512]{2,1,0}"  or  "f32[]"
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# op lines:  "%all-reduce.42 = bf16[...] all-reduce(...)"
_OP_RE = re.compile(
    r"=\s+(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum collective result-shape bytes per collective kind.

    '-start' ops are counted; their '-done' twins are skipped to avoid double
    counting async pairs.
    """
    totals: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        # Result shape(s): everything between '=' and the op name.
        head = line.split("=", 1)[1].split(kind)[0]
        for sm in _SHAPE_RE.finditer(head):
            totals[kind] += _shape_bytes(sm.group(1), sm.group(2))
    return totals


@dataclass
class RooflineReport:
    name: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float                  # 6*N*D (dense) / 6*N_active*D (MoE)
    peak_flops: float = PEAK_BF16
    per_collective: Dict[str, int] = field(default_factory=dict)
    bytes_per_device: float = 0.0       # from memory_analysis

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * self.peak_flops)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flop_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs: how much compiled compute is useful."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the chip's peak the step would achieve if it ran at
        the bound given by the dominant term (MFU-at-bound)."""
        if self.t_bound <= 0:
            return 0.0
        return self.model_flops / (self.t_bound * self.chips * self.peak_flops)

    def row(self) -> dict:
        return {
            "name": self.name,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops,
            "useful_flop_ratio": self.useful_flop_ratio,
            "roofline_fraction": self.roofline_fraction,
            "bytes_per_device": self.bytes_per_device,
        }


def _cost_get(cost, key: str) -> float:
    if cost is None:
        return 0.0
    return float(cost.get(key, 0.0))


def report_from_compiled(name: str, compiled, hlo_text: str,
                         chips: int, model_flops: float,
                         peak_flops: float = PEAK_BF16) -> RooflineReport:
    """`hlo_text` must be the POST-SPMD module (compiled.as_text()):
    collectives only exist after partitioning.  cost_analysis() of the
    compiled artifact reports the PER-DEVICE module, so flops/bytes/
    collective bytes are scaled by `chips` to make the report global --
    the three terms then divide back by chips per the assignment formulas."""
    cost = compiled.cost_analysis()
    flops = _cost_get(cost, "flops") * chips
    byts = _cost_get(cost, "bytes accessed") * chips
    per = {k: v * chips for k, v in parse_collective_bytes(hlo_text).items()}
    mem = 0.0
    try:
        ma = compiled.memory_analysis()
        mem = float(getattr(ma, "argument_size_in_bytes", 0)
                    + getattr(ma, "output_size_in_bytes", 0)
                    + getattr(ma, "temp_size_in_bytes", 0))
    except Exception:
        pass
    return RooflineReport(
        name=name, chips=chips, hlo_flops=flops, hlo_bytes=byts,
        collective_bytes=float(sum(per.values())), model_flops=model_flops,
        peak_flops=peak_flops, per_collective=per, bytes_per_device=mem)


def fmt_seconds(s: float) -> str:
    if s >= 1.0:
        return f"{s:.3f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.3f}ms"
    return f"{s * 1e6:.1f}us"


def fmt_table(reports) -> str:
    hdr = (f"{'cell':<38}{'chips':>6}{'compute':>12}{'memory':>12}"
           f"{'collect':>12}{'bound':>11}{'useful':>8}{'roofl%':>8}{'GB/dev':>8}")
    lines = [hdr, "-" * len(hdr)]
    for r in reports:
        lines.append(
            f"{r.name:<38}{r.chips:>6}"
            f"{fmt_seconds(r.t_compute):>12}{fmt_seconds(r.t_memory):>12}"
            f"{fmt_seconds(r.t_collective):>12}{r.bottleneck:>11}"
            f"{r.useful_flop_ratio:>8.2f}{100 * r.roofline_fraction:>7.1f}%"
            f"{r.bytes_per_device / 2**30:>8.2f}")
    return "\n".join(lines)
