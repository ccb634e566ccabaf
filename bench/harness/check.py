"""The comparison that decides ``correct``.

One job of the window, drawn from the run's seed, is replayed by the plain
reference (``reference.train``) and compared on three numbers:

* ``loss_gap``: the largest relative gap, over the job's epochs, between
  the objective the program reported after the epoch and the reference's
  objective at its own weights after that epoch;
* ``w_norm_gap``: the gap between the norms of the trained weights (their
  change from w = 0), relative to the reference's norm;
* ``w_dist``: the distance between the trained weights and the
  reference's, relative to the reference's norm.

Each has a limit of its own in ``bench/checks/<cell>.json``, set from
readings of sound runs and of the control (see PERF.md).
"""
from __future__ import annotations

import math
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

from . import reference

NUMBERS = ("loss_gap", "w_norm_gap", "w_dist")


def numbers(w, history: Sequence[float], w_ref: np.ndarray,
            f_ref: Sequence[float]) -> Dict[str, float]:
    w = np.asarray(w, np.float64)
    w_ref = np.asarray(w_ref, np.float64)
    ref_norm = float(np.linalg.norm(w_ref))
    if history is None or len(history) != len(f_ref):
        loss_gap = math.inf
    else:
        loss_gap = max(abs(float(h) - f) / abs(f)
                       for h, f in zip(history, f_ref))
    return {"loss_gap": loss_gap,
            "w_norm_gap": abs(float(np.linalg.norm(w)) - ref_norm) / ref_norm,
            "w_dist": float(np.linalg.norm(w - w_ref)) / ref_norm}


def replay(config: Dict, traffic: Dict, corpus: Path, seed: int,
           arith: reference.Arith = reference.REFERENCE):
    """(weights, per-epoch objectives) of the job with ``seed``."""
    data = reference.open_corpus(config, corpus, arith)
    return reference.train(config, traffic, data, seed)


def verdict(nums: Dict[str, float], checks: Dict) -> (bool, List[Dict]):
    """(all numbers within their limits, [{name, value, limit}])."""
    rows = [{"name": k, "value": nums.get(k, math.nan),
             "limit": checks[k]["limit"]} for k in NUMBERS]
    ok = all(math.isfinite(r["value"]) and r["value"] <= r["limit"]
             for r in rows)
    return ok, rows
