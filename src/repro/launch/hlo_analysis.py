"""Post-SPMD HLO analysis: collective-traffic extraction + roofline terms.

``cost_analysis()`` gives HLO FLOPs and bytes accessed but NOT collective
traffic, so we parse the optimized HLO text and sum the bytes moved by every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
with ring-algorithm multipliers and participant counts from replica_groups.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(type_str: str) -> int:
    m = _SHAPE_RE.match(type_str.strip())
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dt, 4)


def _result_bytes(line: str) -> int:
    """Bytes of the op result: handles tuple results ( ... , ... )."""
    m = re.search(r"=\s+(\(?)(.*?)\s+(all-gather|all-reduce|reduce-scatter|"
                  r"all-to-all|collective-permute)", line)
    if not m:
        return 0
    tup, types, _ = m.groups()
    if tup:
        types = types.rstrip(")")
        return sum(_shape_bytes(t) for t in types.split(", ") if "[" in t)
    return _shape_bytes(types)


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return total_devices


@dataclasses.dataclass
class CollectiveStats:
    # per-device bytes moved over ICI, by collective kind
    by_kind: Dict[str, float]
    op_counts: Dict[str, int]

    @property
    def total_bytes(self) -> float:
        return sum(self.by_kind.values())


def collective_bytes(hlo_text: str, total_devices: int) -> CollectiveStats:
    """Per-device ICI traffic with ring-collective multipliers:

    all-gather:       result*(n-1)/n received per device
    all-reduce:       2*size*(n-1)/n (reduce-scatter + all-gather phases)
    reduce-scatter:   input*(n-1)/n = result*(n-1)
    all-to-all:       size*(n-1)/n
    collective-permute: full size
    """
    by_kind = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("//"):
            continue
        kind = None
        for k in COLLECTIVES:
            if re.search(rf"\s{k}(-start)?\(", line) or \
               re.search(rf"=\s*\S*\s*{k}(-start)?\(", line):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done" in line:
            continue
        size = _result_bytes(line)
        if size == 0:
            continue
        n = max(_group_size(line, total_devices), 1)
        frac = (n - 1) / n
        if kind == "all-gather":
            moved = size * frac
        elif kind == "all-reduce":
            moved = 2.0 * size * frac
        elif kind == "reduce-scatter":
            moved = size * (n - 1)
        elif kind == "all-to-all":
            moved = size * frac
        else:  # collective-permute
            moved = float(size)
        by_kind[kind] += moved
        counts[kind] += 1
    return CollectiveStats(by_kind, counts)


def cost_dict(compiled) -> Dict[str, float]:
    """The numeric entries of compiled.cost_analysis()."""
    return {k: float(v) for k, v in compiled.cost_analysis().items()
            if isinstance(v, (int, float, np.floating))}


def memory_dict(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    return out


@dataclasses.dataclass
class Roofline:
    """Three-term roofline for one compiled program on one mesh.

    ``flops`` / ``hbm_bytes`` come from cost_analysis() of the compiled SPMD
    module, which is the PER-DEVICE program — so the terms below are already
    per-chip seconds without dividing by chip count.
    """
    chips: int
    flops: float                  # HLO FLOPs per device
    hbm_bytes: float              # HLO bytes accessed per device
    ici_bytes_per_chip: float     # per-device collective traffic
    peak_flops: float
    hbm_bw: float
    ici_bw: float

    @property
    def compute_s(self) -> float:
        return self.flops / self.peak_flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.ici_bytes_per_chip / self.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Perfect-overlap estimate: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> Dict[str, float]:
        return {
            "chips": self.chips,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "ici_bytes_per_chip": self.ici_bytes_per_chip,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_s": self.step_s,
        }
