"""The plain reference of one training job, and its lower-precision control.

A job trains l2-regularised ERM from w = 0 for E epochs with a constant
step 1/L.  The reference replays that job from the configuration alone:
the corpus files as the generator wrote them, the batch schedule derived
from the job's seed by the sampling scheme's published rule, and the
solver's update written out in numpy.  It imports nothing of the program
and takes nothing the program made.

``Arith`` says how it computes.  ``REFERENCE`` is float64 throughout.
``CONTROL`` stores every array and every result in bfloat16 and sums in
float32, the way a bf16 matrix unit does: the next precision below the
float32 the configurations state.

Batch schedule, per the sampling scheme and where the corpus lives:

* resident (the corpus on the device): epoch keys are
  ``key, sub = split(key)`` from ``PRNGKey(seed)``; systematic block starts
  are ``permutation(sub, m) * b`` and a block past the end is clamped to
  ``rows - b`` (``lax.dynamic_slice``); random rows are
  ``permutation(sub, rows)``, the last batch padded from its front; cyclic
  blocks are ``j * b``.
* streamed (batches read from host storage): epoch ``e`` draws
  ``default_rng(SeedSequence([seed, e])).permutation(...)`` of the m block
  starts (systematic, rows ``start + i`` wrapping past the end) or of the
  rows (random, padded from the front); cyclic blocks are ``j * b``.

The gradient table of SAG/SAGA is indexed by the batch's position ``j``
in its epoch.
"""
from __future__ import annotations

import dataclasses
import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Tuple

import numpy as np

_EVAL_ROWS = 1 << 18
_THREADS = 8


@dataclasses.dataclass(frozen=True)
class Arith:
    name: str
    dtype: type
    q: Callable[[np.ndarray], np.ndarray]   # rounding of a stored result


def _bf16(a):
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


REFERENCE = Arith("float64", np.float64, lambda a: a)
CONTROL = Arith("bfloat16", np.float32, _bf16)


# ---------------------------------------------------------------------------
# the corpus, read from its files
# ---------------------------------------------------------------------------

class Dense:
    def __init__(self, path: Path, ar: Arith):
        meta = json.loads(Path(str(path) + ".meta.json").read_text())
        self.rows, self.features = meta["rows"], meta["row_dim"] - 1
        # one read into memory: batches then cost no page faults
        self.mm = np.fromfile(path, np.float32).reshape(meta["rows"],
                                                        meta["row_dim"])
        self.ar = ar
        self._step = None       # the training loop's batch buffer

    def batch(self, rows, out: bool = False
              ) -> Tuple[np.ndarray, np.ndarray]:
        """(X, y) of ``rows``; with ``out`` in a buffer the next such call
        overwrites (no fresh pages per training step)."""
        src = self.mm[rows]
        if out:
            if self._step is None or self._step.shape != src.shape:
                self._step = np.empty(src.shape, self.ar.dtype)
            np.copyto(self._step, src)
            blk = self.ar.q(self._step)
        else:
            blk = self.ar.q(src.astype(self.ar.dtype))
        return blk[:, :self.features], blk[:, self.features]

    def row_sq_max(self, first) -> float:
        X = self.mm[:first, :self.features].astype(np.float64)
        return float(np.max(np.sum(X * X, axis=1)))


class CSR:
    def __init__(self, path: Path, ar: Arith):
        import scipy.sparse
        path = Path(path)
        meta = json.loads((path / "meta.json").read_text())
        self.rows, self.features = meta["rows"], meta["row_dim"]
        indptr = np.fromfile(path / "indptr.bin", np.int64)
        values = ar.q(np.fromfile(path / "values.bin", np.float32))
        self.X = scipy.sparse.csr_matrix(
            (values.astype(ar.dtype), np.fromfile(path / "indices.bin",
                                                  np.int32), indptr),
            shape=(self.rows, self.features))
        self.y = ar.q(np.fromfile(path / "labels.bin",
                                  np.float32)).astype(ar.dtype)
        self.ar = ar

    def batch(self, rows, out: bool = False):
        return self.X[rows], self.y[rows]

    def row_sq_max(self, first) -> float:
        X = self.X[:first].astype(np.float64)
        return float(np.max(np.asarray(X.multiply(X).sum(axis=1))))


def open_corpus(config: Dict, path: Path, ar: Arith):
    return (CSR if config["corpus"]["format"] == "csr" else Dense)(path, ar)


# ---------------------------------------------------------------------------
# the batch schedule
# ---------------------------------------------------------------------------

def _resident_epoch_keys(seed: int, epochs: int):
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.PRNGKey(seed)
        subs = []
        for _ in range(epochs):
            key, sub = jax.random.split(key)
            subs.append(sub)
        return subs


def _resident_perm(sub, size: int) -> np.ndarray:
    import jax
    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(jax.random.permutation(sub, size), np.int64)


def epochs_of_batches(scheme: str, placement: str, rows: int, b: int,
                      seed: int, epochs: int) -> Iterator[List]:
    """Per epoch, the list of its m batches; each a ``slice`` of rows or an
    array of row ids."""
    m = -(-rows // b)
    pad = m * b - rows
    keys = (_resident_epoch_keys(seed, epochs) if placement == "resident"
            else None)
    for e in range(epochs):
        if scheme == "cyclic":
            starts = np.arange(m) * b
        elif placement == "resident":
            perm = _resident_perm(keys[e], m if scheme == "systematic"
                                  else rows)
            starts = perm * b if scheme == "systematic" else None
        else:
            rng = np.random.default_rng(np.random.SeedSequence([seed, e]))
            perm = rng.permutation(m if scheme == "systematic" else rows)
            starts = perm * b if scheme == "systematic" else None
        if starts is None:                       # random rows
            ids = np.concatenate([perm, perm[:pad]])
            yield [ids[j * b:(j + 1) * b] for j in range(m)]
        elif placement == "resident":            # dynamic_slice clamps
            yield [slice(s, s + b) for s in np.minimum(starts, rows - b)]
        else:                                    # wrap past the end
            yield [slice(s, s + b) if s + b <= rows
                   else (s + np.arange(b)) % rows for s in starts]


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

def _dloss(z, y):
    with np.errstate(over="ignore"):
        return -y / (1.0 + np.exp(y * z))


def objective(data, w: np.ndarray, reg: float) -> float:
    """Mean logistic loss over the corpus plus (reg/2)||w||^2."""
    q = data.ar.q

    def part(lo: int) -> float:
        Xc, yc = data.batch(slice(lo, lo + _EVAL_ROWS))
        per = q(np.logaddexp(0.0, -yc * q(Xc @ w)))
        return float(np.sum(per, dtype=data.ar.dtype))

    with ThreadPoolExecutor(_THREADS) as ex:
        total = sum(ex.map(part, range(0, data.rows, _EVAL_ROWS)))
    f = total / data.rows + 0.5 * reg * float(w @ w)
    return float(q(np.asarray([f]))[0])


def step_size(config: Dict, data) -> float:
    """1/L with L = 0.25 max_i ||x_i||^2 + reg over the rows the
    configuration names (the logistic loss's curvature bound)."""
    first = config["method"]["lipschitz_rows"]
    return 1.0 / (0.25 * data.row_sq_max(first)
                  + config["problem"]["reg"])


def train(config: Dict, traffic: Dict, data, seed: int
          ) -> Tuple[np.ndarray, List[float]]:
    """(weights after the job, objective after each epoch)."""
    ar, q = data.ar, data.ar.q
    meth = config["method"]
    reg, b, solver = config["problem"]["reg"], meth["batch_size"], \
        meth["solver"]
    if config["problem"]["loss"] != "logistic" or solver not in ("saga",
                                                                 "mbsgd"):
        raise ValueError(f"the reference has no {config['problem']['loss']}"
                         f"/{solver} job")
    n, rows = data.features, data.rows
    m = -(-rows // b)
    alpha = step_size(config, data)
    w = np.zeros(n, ar.dtype)
    table = np.zeros((m, n), ar.dtype) if solver == "saga" else None
    tmean = np.zeros(n, ar.dtype)
    history = []
    for batches in epochs_of_batches(traffic["scheme"], traffic["placement"],
                                     rows, b, seed, traffic["epochs"]):
        for j, sel in enumerate(batches):
            Xb, yb = data.batch(sel, out=True)
            s = q(_dloss(q(Xb @ w), yb) / b)
            g = q(q(Xb.T @ s) + q(reg * w))
            if table is not None:
                old = table[j].copy()
                v = q(g - old + tmean)
                tmean = q(tmean + q((g - old) / m))
                table[j] = g
            else:
                v = g
            w = q(w - q(alpha * v))
        history.append(objective(data, w, reg))
    return w, history
