"""Required work of a training job, counted from shapes.

Each sampled row is read once and its margin and gradient are computed
once; the solver reads and writes its state once per batch.  ELL padding,
relayout copies, warm-up epochs and objective passes are not required work
and are not counted.  A float is 4 bytes, an index 4, a CSR row pointer 8.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict

F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def seconds(self, peak: Dict) -> float:
        """Least time at the chip's peaks: the larger of the two bounds."""
        return max(self.flops / peak["flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def state_per_batch(solver: str, n: int) -> Work:
    """Solver state touched by one update: w read and written, plus the
    gradient table row and its running mean for SAG/SAGA."""
    vectors = 2 + (4 if solver in ("sag", "saga") else 0)
    return Work(flops=4.0 * n, bytes=float(vectors * n * F32))


def dense_batch(b: int, n: int) -> Work:
    """Margins and gradient of one dense batch: b rows of n features and a
    label, 2bn multiply-adds each way."""
    return Work(flops=4.0 * b * n, bytes=float(b * (n + 1) * F32))


def job(config: Dict, traffic: Dict, corpus: Path) -> Work:
    """Required work of one job of ``traffic['epochs']`` epochs."""
    meth = config["method"]
    b, solver = meth["batch_size"], meth["solver"]
    c = config["corpus"]
    rows = c["rows"]
    m = -(-rows // b)
    state = state_per_batch(solver, c["features"]) * m
    if c["format"] == "csr":
        # the m batches of an epoch read m*b rows of the average length;
        # each nonzero is a value and a column id, each row a pointer and
        # a label
        meta = json.loads((Path(corpus) / "meta.json").read_text())
        nnz = meta["nnz"] * m * b / rows
        epoch = Work(4.0 * nnz, nnz * (F32 + 4)
                     + m * ((b + 1) * 8 + b * F32)) + state
    else:
        epoch = dense_batch(b, c["features"]) * m + state
    return epoch * traffic["epochs"]


def peaks(device_kind: str, table: Path = Path(__file__).with_name(
        "peaks.json")) -> Dict:
    """The chip's published peaks; an unknown kind is an error."""
    rows = json.loads(Path(table).read_text())["devices"]
    if device_kind not in rows:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(rows)}")
    return rows[device_kind]
