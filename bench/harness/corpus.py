"""The benchmark's own corpus generators and their stamped cache.

Both draw from ``--seed`` in chunks keyed by ``(seed, chunk)``, on a few
threads, and write the on-disk layouts the program reads (a flat float32
row file with ``.meta.json`` beside it; a CSR directory of ``indptr``,
``indices``, ``values``, ``labels`` and ``meta.json``):

* ``dense_logistic``: rows ``[x, y]``, x ~ N(0, 1) float32, labels from a
  logistic model ``P(y=1) = sigmoid(separation * x.w_true)`` with
  ``w_true ~ N(0, 1/n)`` (the distribution of ``dataset.synth_erm_corpus``);
* ``csr_logistic``: row lengths ~ Binomial(features, density) clipped to
  >= 1, distinct sorted column ids, N(0, 1) values and logistic labels with
  ``w_true ~ N(0, 1/(features * density))`` (the distribution of
  ``sparse.synth_sparse_classification``), without ever drawing a
  ``(rows, features)`` matrix.

A corpus is kept under ``<cache>/<config>/`` with a stamp of its shape,
seed and the generator's source; a later run with the same stamp reuses
it, any other replaces it, so one corpus per configuration is on disk.
"""
from __future__ import annotations

import hashlib
import inspect
import json
import shutil
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

_THREADS = 8
_DENSE_CHUNK = 1 << 20          # rows per dense chunk
_CSR_CHUNK = 1 << 16            # rows per CSR chunk
_CSR_SPARE = 16                 # extra candidate ids drawn per row


def _streams(seed: int, chunks: int):
    """One generator for ``w_true`` and one per chunk, from ``seed``."""
    kids = np.random.SeedSequence(seed).spawn(chunks + 1)
    return [np.random.default_rng(k) for k in kids]


def _labels(rng, z: np.ndarray, separation: float) -> np.ndarray:
    p = 1.0 / (1.0 + np.exp(-separation * z))
    return np.where(rng.random(z.shape[0]) < p, 1.0, -1.0).astype(np.float32)


def dense_logistic(path: Path, seed: int, *, rows: int, features: int,
                   separation: float) -> Path:
    """Write a dense corpus to the file ``path``."""
    chunks = -(-rows // _DENSE_CHUNK)
    rngs = _streams(seed, chunks)
    w_true = rngs[0].standard_normal(features) / np.sqrt(features)
    out = np.memmap(path, dtype=np.float32, mode="w+",
                    shape=(rows, features + 1))

    def fill(k: int):
        lo = k * _DENSE_CHUNK
        hi = min(rows, lo + _DENSE_CHUNK)
        rng = rngs[k + 1]
        X = rng.standard_normal((hi - lo, features), dtype=np.float32)
        out[lo:hi, :features] = X
        out[lo:hi, features] = _labels(rng, X.astype(np.float64) @ w_true,
                                       separation)

    with ThreadPoolExecutor(_THREADS) as ex:
        list(ex.map(fill, range(chunks)))
    out.flush()
    del out
    meta = {"kind": "rows", "rows": rows, "row_dim": features + 1,
            "dtype": "float32"}
    path.with_suffix(path.suffix + ".meta.json").write_text(json.dumps(meta))
    return path


def _distinct_ids(rng, k: np.ndarray, features: int) -> np.ndarray:
    """(c, max k) int32: row i holds k[i] distinct ids, sorted, then
    ``features`` as padding.  The first k[i] distinct values of a stream of
    uniform draws are a uniform k[i]-subset."""
    c, kc = k.shape[0], int(k.max())
    width = min(features, kc + _CSR_SPARE)
    while True:
        cand = rng.integers(0, features, size=(c, width), dtype=np.int32)
        order = np.argsort(cand, axis=1, kind="stable")
        srt = np.take_along_axis(cand, order, axis=1)
        dup_sorted = np.zeros_like(srt, dtype=bool)
        dup_sorted[:, 1:] = srt[:, 1:] == srt[:, :-1]
        dup = np.empty_like(dup_sorted)
        np.put_along_axis(dup, order, dup_sorted, axis=1)
        rank = np.cumsum(~dup, axis=1)            # 1-based among distinct
        keep = ~dup & (rank <= k[:, None])
        if (keep.sum(axis=1) == k).all():
            break
        if width == features:
            raise ValueError(f"rows of up to {kc} of {features} ids are "
                             "too dense for this generator")
        width = min(features, width * 2)          # rare: too many repeats
    ids = np.sort(np.where(keep, cand, features), axis=1)[:, :kc]
    return ids


def csr_logistic(path: Path, seed: int, *, rows: int, features: int,
                 density: float, separation: float) -> Path:
    """Write a CSR corpus to the directory ``path``."""
    chunks = -(-rows // _CSR_CHUNK)
    rngs = _streams(seed, chunks)
    w_true = (rngs[0].standard_normal(features)
              / np.sqrt(max(1.0, features * density)))
    w_ext = np.append(w_true, 0.0)

    def make(k_: int):
        lo = k_ * _CSR_CHUNK
        c = min(rows, lo + _CSR_CHUNK) - lo
        rng = rngs[k_ + 1]
        k = rng.binomial(features, density, size=c).clip(1, features)
        ids = _distinct_ids(rng, k, features)
        valid = ids < features
        vals = rng.standard_normal(ids.shape, dtype=np.float32)
        z = np.sum(np.where(valid, vals, 0.0) * w_ext[ids], axis=1)
        return (ids[valid], vals[valid], _labels(rng, z, separation),
                k.astype(np.int64))

    with ThreadPoolExecutor(_THREADS) as ex:
        parts = list(ex.map(make, range(chunks)))
    path.mkdir(parents=True, exist_ok=True)
    lens = np.concatenate([p[3] for p in parts])
    indptr = np.zeros(rows + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    for name, arrs, dt in (("indices.bin", [p[0] for p in parts], np.int32),
                           ("values.bin", [p[1] for p in parts], np.float32),
                           ("labels.bin", [p[2] for p in parts], np.float32)):
        with open(path / name, "wb") as f:
            for a in arrs:
                np.asarray(a, dt).tofile(f)
    indptr.tofile(path / "indptr.bin")
    meta = {"kind": "sparse_rows", "rows": rows, "row_dim": features,
            "dtype": "float32", "fmt": "csr", "nnz": int(indptr[-1]),
            "max_row_nnz": int(lens.max())}
    (path / "meta.json").write_text(json.dumps(meta))
    return path


GENERATORS = {"dense_logistic": dense_logistic, "csr_logistic": csr_logistic}


def stamp(config: Dict, seed: int) -> Dict:
    """What a cached corpus must match to be reused."""
    gen = GENERATORS[config["corpus"]["generator"]]
    src = (inspect.getsource(gen) + inspect.getsource(_labels)
           + inspect.getsource(_streams)
           + inspect.getsource(_distinct_ids)).encode()
    return {"config": config["name"], "corpus": config["corpus"],
            "seed": int(seed), "source_sha256": hashlib.sha256(src).hexdigest()}


def ensure(config: Dict, seed: int, cache: Path) -> Tuple[Path, bool]:
    """(corpus path, whether it was generated now) for ``config`` at
    ``seed``, reusing the cached one when its stamp matches."""
    home = Path(cache) / config["name"]
    want = stamp(config, seed)
    stamp_path = home / "stamp.json"
    spec = dict(config["corpus"])
    gen = GENERATORS[spec.pop("generator")]
    spec.pop("format", None)
    path = home / ("corpus" if gen is csr_logistic else "corpus.bin")
    if stamp_path.exists() and json.loads(stamp_path.read_text()) == want:
        return path, False
    shutil.rmtree(home, ignore_errors=True)
    home.mkdir(parents=True)
    gen(path, seed, **spec)
    stamp_path.write_text(json.dumps(want))   # last: a half corpus never hits
    return path, True
