"""Faults planted in the program under test, to show that the comparison
fails them.  Each is a context manager that patches the program's timed
path and clears JAX's caches on entry and exit, so what runs inside is
traced anew with the fault and nothing faulty outlives it.

* ``state_unchanged``: every solver step returns its state as it got it.
* ``half_batch``: every step's data gradient is the mean over the first
  half of its batch; the other half is left out.
* ``no_exchange``: under a mesh's ``gather`` reduction, each chip keeps only
  its own shard of a staged chunk; the rows the all-gather would have
  brought from the other chips are zeros.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator


def _clear():
    import jax
    from repro.core import solvers
    jax.clear_caches()
    solvers.make_epoch_fn.cache_clear()
    solvers.make_supercell_epoch_fn.cache_clear()


@contextlib.contextmanager
def _patched(patches: Dict) -> Iterator[None]:
    saved = {(obj, name): getattr(obj, name) for obj, name in patches}
    _clear()
    try:
        for (obj, name), value in patches.items():
            setattr(obj, name, value)
        yield
    finally:
        for (obj, name), value in saved.items():
            setattr(obj, name, value)
        _clear()


def state_unchanged():
    from repro.core import solvers
    keep = lambda problem, cfg, state, *a, **k: state  # noqa: E731
    return _patched({(solvers, "batch_step"): keep,
                     (solvers, "sparse_batch_step"): keep,
                     (solvers, "fused_batch_step"): keep})


def half_batch():
    from repro.core.erm import ERMProblem
    from repro.kernels import fused_erm
    dense, ell = ERMProblem.batch_grad_data, ERMProblem.ell_batch_grad_data
    fused = fused_erm.fused_batch_grad_data

    def dense_half(self, w, Xb, yb):
        h = Xb.shape[0] // 2
        return dense(self, w, Xb[:h], yb[:h])

    def ell_half(self, w, cols, vals, yb):
        h = cols.shape[0] // 2
        return ell(self, w, cols[:h], vals[:h], yb[:h])

    def fused_half(problem, X, y, w, *, start=None, idx=None,
                   batch_size=None, **kw):
        if start is not None:
            return fused(problem, X, y, w, start=start,
                         batch_size=batch_size // 2, **kw)
        return fused(problem, X, y, w, idx=idx[:idx.shape[0] // 2], **kw)

    return _patched({(ERMProblem, "batch_grad_data"): dense_half,
                     (ERMProblem, "ell_batch_grad_data"): ell_half,
                     (fused_erm, "fused_batch_grad_data"): fused_half})


def no_exchange():
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distributed import sharding
    make = sharding.make_staging_put

    def make_put(mesh, batch_axes, gather=False, stats=None, tracer=None):
        put = make(mesh, batch_axes, gather=False, stats=stats,
                   tracer=tracer)
        if not gather:
            return put
        replicated = NamedSharding(mesh, PartitionSpec())

        def local_only(host):
            out = []
            for arr in put(host):
                shards = []
                for s in arr.addressable_shards:
                    full = np.zeros(arr.shape, arr.dtype)
                    full[s.index] = np.asarray(s.data)
                    shards.append(jax.device_put(full, s.device))
                out.append(jax.make_array_from_single_device_arrays(
                    arr.shape, replicated, shards))
            return tuple(out)
        return local_only

    return _patched({(sharding, "make_staging_put"): make_put})


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}
