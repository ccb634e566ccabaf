"""Fused CSR mini-batch gradient kernels — the sparse epoch engine's hot path.

The dense fused kernels (``fused_erm``) DMA (b, n) row blocks; on the
paper's sparse datasets (news20 ~0.03% nnz) that moves 3000x more bytes
than the data contains.  These kernels compute the data-term gradient

    g_data = (1/b) * Xb^T s,   s_i = dloss/dz(z_i, y_i),   z_i = x_i . w

directly from CSR storage resident in HBM — flat ``values``/``indices``
arrays plus ``indptr`` — and the two access patterns keep their structural
signature at the DMA level, mirroring what :class:`SparsePipeline` does at
the storage level:

* :func:`sparse_grad_rows` (RS): a grid of b steps, each DMA-ing ONE row's
  nonzero segment (a ``kmax``-padded window at ``indptr[row]``) — the
  per-row descriptor cost that makes RS slow, with nnz-proportional bytes.
* :func:`sparse_grad_block` (CS/SS): ONE contiguous window DMA covering the
  whole batch range ``[indptr[start], indptr[start+b])`` — the single-seek
  analogue, again nnz-proportional.

Inside the kernel each row is densified in VMEM via a one-hot contraction
— never in HBM — one FEATURE TILE at a time (``(1, K) @ (K, tn)`` on the
MXU, ``tn`` from :func:`fused_erm._feature_tile`): the margin pass runs
over all tiles first (z needs every feature), then a second tile pass
emits the rank-1 gradient update, so VMEM holds O(K * tn) floats instead
of O(K * n) and news20-scale feature counts (1.3M) fit.  ``K`` is the
corpus's densest row rounded up to lane width.

:func:`sparse_margins_block` / :func:`sparse_margins_rows` expose the
margin pass stand-alone — the CSR counterpart of
``fused_erm.fused_batch_margins``, parity-tested and staged for the
ROADMAP's sparse RESIDENT mode (today's streamed CSR engine runs line
search on materialized padded-ELL batches via
``step_rules.ell_probe``, which is already nnz-proportional).

Semantics contract (tested in ``tests/test_sparse_erm.py``):

* block: rows ``[start', start'+b)`` with ``start' = clip(start, 0, l-b)``
  — identical clamping to ``fused_grad_block``/``lax.dynamic_slice``.
* rows: exactly the rows of ``idx`` (duplicates and wrap-around included),
  matching ``gather_batch`` on the densified corpus.
* parity: equals ``fused_batch_grad_data`` on ``CSRCorpus.densify()`` to
  <= 1e-5 for all three losses and all three schemes.

``interpret=None`` runs the interpreter on the CPU backend only.  These
kernels have never been compiled for a TPU (they are on no live path); the
host-side scipy/numpy fallbacks for streamed full-corpus passes live in
``repro.data.sparse``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.erm import ERMProblem
from .fused_erm import _dloss, _feature_tile, _resolve_interpret

# one-hot densify scratch is (K, tn) float32 per feature tile; keep it well
# under VMEM
_VMEM_ONEHOT_BUDGET = 8 << 20


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _check_onehot_fits(K: int, tn: int):
    if K * tn * 4 > _VMEM_ONEHOT_BUDGET:
        raise ValueError(
            f"one-hot densify scratch ({K}x{tn} f32) exceeds the VMEM "
            f"budget even after feature tiling (no divisor of the feature "
            f"count in the tile range) — pad the corpus width to a "
            f"tileable size")


def _ensure_tail(flat: jax.Array, nnz: Optional[int], window: int) -> jax.Array:
    """Guarantee ``window`` elements of slack after the nonzeros so DMA
    windows starting at any valid offset stay in bounds.

    When the caller staged pre-padded arrays (``csr_to_device``) and passed
    their static ``nnz``, this is a no-op — the O(nnz) pad copy happens
    ONCE at staging, not per mini-batch gradient.  Without ``nnz`` the
    padding is applied here (correct, but a per-call whole-corpus copy).
    """
    if nnz is not None and flat.shape[-1] >= nnz + window:
        return flat
    return jnp.pad(flat, (0, window))


def _masked_vals(K: int, vrow, ln):
    """(1, K) row values with the junk beyond ``ln`` zeroed — zero values
    kill junk columns in the one-hot contraction, so no column mask is
    ever needed downstream."""
    kiota = jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)
    return jnp.where(kiota < ln, vrow, 0.0).reshape(1, K)


def _row_tile(K: int, tn: int, v1k, crow, t):
    """(1, tn) densified slice of one CSR row for feature tile ``t``: the
    one-hot contraction (1, K) @ (K, tn) on the MXU, restricted to columns
    in ``[t*tn, (t+1)*tn)`` — each stored column matches exactly its own
    tile, so summing tiles reproduces the full-width densify."""
    c0 = t * tn
    onehot = ((crow - c0) == jax.lax.broadcasted_iota(jnp.int32, (K, tn), 1)
              ).astype(jnp.float32)
    return jnp.dot(v1k, onehot, preferred_element_type=jnp.float32)


def _row_margin(K: int, tn: int, nt: int, v1k, crow, w_ref):
    """z = x_i . w accumulated across feature tiles."""
    def body(t, z):
        r = _row_tile(K, tn, v1k, crow, t)
        return z + jnp.sum(r * w_ref[0, pl.ds(t * tn, tn)].reshape(1, tn))
    return jax.lax.fori_loop(0, nt, body, jnp.float32(0.0))


def _accumulate_row(loss: str, b: int, K: int, tn: int, n: int, vrow, crow,
                    ln, y_i, w_ref, g_ref):
    """Densify one CSR row in VMEM — one feature tile at a time — and
    accumulate its gradient contribution.

    ``vrow``/``crow``: (K, 1) value/column windows (junk beyond ``ln``).
    Tiling (``tn`` from :func:`fused_erm._feature_tile`) caps the one-hot
    scratch at (K, tn) instead of (K, n), which is what lets news20-scale
    feature counts (1.3M) fit VMEM; the margin pass runs over all tiles
    first (z needs every feature), then a second tile pass emits the
    rank-1 gradient update — the densified tile is recomputed rather than
    kept, trading one extra MXU contraction per tile for O(K * tn) scratch.
    """
    nt = n // tn
    v1k = _masked_vals(K, vrow, ln)
    z = _row_margin(K, tn, nt, v1k, crow, w_ref)
    s_i = _dloss(loss, z, y_i) / b

    def body(t, carry):
        r = _row_tile(K, tn, v1k, crow, t)
        g_ref[0, pl.ds(t * tn, tn)] += (s_i * r).reshape(tn)
        return carry
    jax.lax.fori_loop(0, nt, body, 0)


# ---------------------------------------------------------------------------
# RS: per-row segment DMA grid
# ---------------------------------------------------------------------------

def _rows_kernel(loss: str, b: int, K: int, tn: int, n: int,
                 seg_start_ref, seg_len_ref, vals_hbm, cols_hbm, yb_ref,
                 w_ref, g_ref, vals_w, cols_w, sems):
    i = pl.program_id(0)   # one sampled row per grid step
    s = seg_start_ref[i]
    # ONE (1, K) window DMA per row at this row's segment start: the
    # scattered, per-descriptor access pattern RS pays for — but only
    # kmax-padded nnz bytes, never the dense (1, n) row.
    dv = pltpu.make_async_copy(vals_hbm.at[:, pl.ds(s, K)], vals_w,
                               sems.at[0])
    dc = pltpu.make_async_copy(cols_hbm.at[:, pl.ds(s, K)], cols_w,
                               sems.at[1])
    dv.start()
    dc.start()

    @pl.when(i == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    dv.wait()
    dc.wait()
    _accumulate_row(loss, b, K, tn, n, vals_w[...].reshape(K, 1),
                    cols_w[...].reshape(K, 1), seg_len_ref[i],
                    yb_ref[0, i], w_ref, g_ref)


@functools.partial(jax.jit, static_argnames=("loss", "kmax", "nnz",
                                             "interpret"))
def sparse_grad_rows(vals: jax.Array, cols: jax.Array, indptr: jax.Array,
                     y: jax.Array, w: jax.Array, idx: jax.Array, *,
                     loss: str, kmax: int, nnz: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Data-term gradient of the scattered CSR batch ``rows[idx]`` (RS).

    ``vals``/``cols``: flat (nnz,) CSR arrays, ``indptr``: (l+1,),
    ``y``: (l,), ``w``: (n,), ``idx``: (b,) row ids, ``kmax``: densest row
    (static — sizes the per-row DMA window).  Returns (n,) float32
    ``(1/b) Xb^T dloss(Xb w, yb)`` — no regularizer.
    """
    n = w.shape[0]
    b = idx.shape[0]
    K = _round_up(max(kmax, 1), 128)
    tn = _feature_tile(n)
    _check_onehot_fits(K, tn)
    ip = indptr.astype(jnp.int32)
    idx32 = idx.astype(jnp.int32)
    seg_start = jnp.take(ip, idx32)
    seg_len = jnp.take(ip, idx32 + 1) - seg_start
    yb = jnp.take(y, idx32).astype(jnp.float32).reshape(1, b)
    # the last row's K-window must stay in bounds (no-op if pre-padded)
    vals_p = _ensure_tail(vals.astype(jnp.float32), nnz, K).reshape(1, -1)
    cols_p = _ensure_tail(cols.astype(jnp.int32), nnz, K).reshape(1, -1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),    # vals stay in HBM
                  pl.BlockSpec(memory_space=pltpu.ANY),    # cols stay in HBM
                  pl.BlockSpec(memory_space=pltpu.VMEM),   # yb (1, b)
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # w (1, n)
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((1, K), jnp.float32),
                        pltpu.VMEM((1, K), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    g = pl.pallas_call(
        functools.partial(_rows_kernel, loss, b, K, tn, n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(seg_start, seg_len, vals_p, cols_p, yb,
      w.reshape(1, n).astype(jnp.float32))
    return g.reshape(n).astype(w.dtype)


# ---------------------------------------------------------------------------
# CS/SS: one contiguous indptr-range window DMA
# ---------------------------------------------------------------------------

def _block_kernel(loss: str, b: int, K: int, EW: int, tn: int, n: int,
                  e0_ref, rowstart_ref, rowlen_ref, vals_hbm, cols_hbm,
                  yb_ref, w_ref, g_ref, vals_seg, cols_seg, sems):
    r = pl.program_id(0)   # one batch row per grid step

    @pl.when(r == 0)
    def _():
        # ONE contiguous window DMA for the WHOLE batch's nonzeros,
        # [indptr[start], indptr[start] + EW) — the single-seek CS/SS
        # signature; rows then slice the VMEM-resident segment.
        e0 = e0_ref[0]
        dv = pltpu.make_async_copy(vals_hbm.at[:, pl.ds(e0, EW)], vals_seg,
                                   sems.at[0])
        dc = pltpu.make_async_copy(cols_hbm.at[:, pl.ds(e0, EW)], cols_seg,
                                   sems.at[1])
        dv.start()
        dc.start()
        dv.wait()
        dc.wait()
        g_ref[...] = jnp.zeros_like(g_ref)

    off = rowstart_ref[r]
    _accumulate_row(loss, b, K, tn, n,
                    vals_seg[0, pl.ds(off, K)].reshape(K, 1),
                    cols_seg[0, pl.ds(off, K)].reshape(K, 1),
                    rowlen_ref[r], yb_ref[0, r], w_ref, g_ref)


@functools.partial(jax.jit, static_argnames=("loss", "batch_size", "kmax",
                                             "nnz", "interpret"))
def sparse_grad_block(vals: jax.Array, cols: jax.Array, indptr: jax.Array,
                      y: jax.Array, w: jax.Array, start: jax.Array, *,
                      loss: str, batch_size: int, kmax: int,
                      nnz: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Data-term gradient of the contiguous CSR batch at row ``start`` (CS/SS).

    ``start`` is clamped to ``[0, l - b]`` exactly like the dense
    ``fused_grad_block``/``lax.dynamic_slice``, so the two paths are
    interchangeable including the overlapping last batch.  Returns (n,)
    float32 data gradient.
    """
    n = w.shape[0]
    l = y.shape[0]
    b = batch_size
    if b > l:
        raise ValueError(f"batch_size {b} > rows {l}")
    K = _round_up(max(kmax, 1), 128)
    tn = _feature_tile(n)
    _check_onehot_fits(K, tn)
    # window covers any batch's nonzeros (<= b*kmax) plus one row-window of
    # slack so the last row's K-slice of the VMEM segment stays in bounds
    EW = _round_up(b * max(kmax, 1) + K, 128)
    ip = indptr.astype(jnp.int32)
    start_c = jnp.clip(start.astype(jnp.int32), 0, l - b)
    ptr = jax.lax.dynamic_slice(ip, (start_c,), (b + 1,))
    e0 = ptr[:1]                         # (1,) absolute element offset
    rowstart = ptr[:-1] - ptr[0]
    rowlen = ptr[1:] - ptr[:-1]
    yb = jax.lax.dynamic_slice(y.astype(jnp.float32), (start_c,),
                               (b,)).reshape(1, b)
    vals_p = _ensure_tail(vals.astype(jnp.float32), nnz, EW).reshape(1, -1)
    cols_p = _ensure_tail(cols.astype(jnp.int32), nnz, EW).reshape(1, -1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM),   # yb (1, b)
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # w (1, n)
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((1, EW), jnp.float32),
                        pltpu.VMEM((1, EW), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    g = pl.pallas_call(
        functools.partial(_block_kernel, loss, b, K, EW, tn, n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(e0, rowstart, rowlen, vals_p, cols_p, yb,
      w.reshape(1, n).astype(jnp.float32))
    return g.reshape(n).astype(w.dtype)


# ---------------------------------------------------------------------------
# batch margins: z = Xb @ w from CSR storage — the sparse line-search
# trial-objective kernel (margin pass of the gradient kernels, stand-alone)
# ---------------------------------------------------------------------------

def _rows_margins_kernel(K: int, tn: int, n: int,
                         seg_start_ref, seg_len_ref, vals_hbm, cols_hbm,
                         w_ref, z_ref, vals_w, cols_w, sems):
    i = pl.program_id(0)   # one sampled row per grid step
    s = seg_start_ref[i]
    dv = pltpu.make_async_copy(vals_hbm.at[:, pl.ds(s, K)], vals_w,
                               sems.at[0])
    dc = pltpu.make_async_copy(cols_hbm.at[:, pl.ds(s, K)], cols_w,
                               sems.at[1])
    dv.start()
    dc.start()
    dv.wait()
    dc.wait()
    v1k = _masked_vals(K, vals_w[...].reshape(K, 1), seg_len_ref[i])
    z_ref[0, i] = _row_margin(K, tn, n // tn, v1k,
                              cols_w[...].reshape(K, 1), w_ref)


@functools.partial(jax.jit, static_argnames=("kmax", "nnz", "interpret"))
def sparse_margins_rows(vals: jax.Array, cols: jax.Array, indptr: jax.Array,
                        w: jax.Array, idx: jax.Array, *, kmax: int,
                        nnz: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Margins ``z_i = rows[idx[i]] . w`` of a scattered CSR batch (RS):
    one per-row segment window DMA per grid step, like
    :func:`sparse_grad_rows`.  Returns (b,) float32."""
    n = w.shape[0]
    b = idx.shape[0]
    K = _round_up(max(kmax, 1), 128)
    tn = _feature_tile(n)
    _check_onehot_fits(K, tn)
    ip = indptr.astype(jnp.int32)
    idx32 = idx.astype(jnp.int32)
    seg_start = jnp.take(ip, idx32)
    seg_len = jnp.take(ip, idx32 + 1) - seg_start
    vals_p = _ensure_tail(vals.astype(jnp.float32), nnz, K).reshape(1, -1)
    cols_p = _ensure_tail(cols.astype(jnp.int32), nnz, K).reshape(1, -1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # w (1, n)
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((1, K), jnp.float32),
                        pltpu.VMEM((1, K), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    z = pl.pallas_call(
        functools.partial(_rows_margins_kernel, K, tn, n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(seg_start, seg_len, vals_p, cols_p,
      w.reshape(1, n).astype(jnp.float32))
    return z.reshape(b).astype(w.dtype)


def _block_margins_kernel(K: int, EW: int, tn: int, n: int,
                          e0_ref, rowstart_ref, rowlen_ref, vals_hbm,
                          cols_hbm, w_ref, z_ref, vals_seg, cols_seg, sems):
    r = pl.program_id(0)   # one batch row per grid step

    @pl.when(r == 0)
    def _():
        e0 = e0_ref[0]
        dv = pltpu.make_async_copy(vals_hbm.at[:, pl.ds(e0, EW)], vals_seg,
                                   sems.at[0])
        dc = pltpu.make_async_copy(cols_hbm.at[:, pl.ds(e0, EW)], cols_seg,
                                   sems.at[1])
        dv.start()
        dc.start()
        dv.wait()
        dc.wait()

    off = rowstart_ref[r]
    v1k = _masked_vals(K, vals_seg[0, pl.ds(off, K)].reshape(K, 1),
                       rowlen_ref[r])
    z_ref[0, r] = _row_margin(K, tn, n // tn, v1k,
                              cols_seg[0, pl.ds(off, K)].reshape(K, 1),
                              w_ref)


@functools.partial(jax.jit, static_argnames=("batch_size", "kmax", "nnz",
                                             "interpret"))
def sparse_margins_block(vals: jax.Array, cols: jax.Array, indptr: jax.Array,
                         w: jax.Array, start: jax.Array, *, batch_size: int,
                         kmax: int, nnz: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Margins of the contiguous CSR batch at row ``start`` (CS/SS): ONE
    whole-batch indptr-range window DMA, like :func:`sparse_grad_block`,
    same ``clip(start, 0, l-b)`` clamping.  Returns (b,) float32."""
    n = w.shape[0]
    l = indptr.shape[0] - 1
    b = batch_size
    if b > l:
        raise ValueError(f"batch_size {b} > rows {l}")
    K = _round_up(max(kmax, 1), 128)
    tn = _feature_tile(n)
    _check_onehot_fits(K, tn)
    EW = _round_up(b * max(kmax, 1) + K, 128)
    ip = indptr.astype(jnp.int32)
    start_c = jnp.clip(start.astype(jnp.int32), 0, l - b)
    ptr = jax.lax.dynamic_slice(ip, (start_c,), (b + 1,))
    e0 = ptr[:1]
    rowstart = ptr[:-1] - ptr[0]
    rowlen = ptr[1:] - ptr[:-1]
    vals_p = _ensure_tail(vals.astype(jnp.float32), nnz, EW).reshape(1, -1)
    cols_p = _ensure_tail(cols.astype(jnp.int32), nnz, EW).reshape(1, -1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # w (1, n)
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((1, EW), jnp.float32),
                        pltpu.VMEM((1, EW), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    z = pl.pallas_call(
        functools.partial(_block_margins_kernel, K, EW, tn, n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(e0, rowstart, rowlen, vals_p, cols_p,
      w.reshape(1, n).astype(jnp.float32))
    return z.reshape(b).astype(w.dtype)


# ---------------------------------------------------------------------------
# device staging + solver-facing wrappers (parity contract with fused_erm)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CSRDevice:
    """Device-resident CSR corpus: the kernels' input layout.

    Flat values/indices stay in HBM (the kernels DMA nnz-proportional
    windows) and carry DMA-window tail padding applied ONCE at staging —
    ``nnz`` (static) lets the wrappers skip their per-call pad fallback.
    ``indptr`` is int32 (nnz < 2^31 asserted at staging).
    """
    vals: jax.Array        # (nnz + pad,) float32
    cols: jax.Array        # (nnz + pad,) int32
    indptr: jax.Array      # (rows+1,) int32
    y: jax.Array           # (rows,) float32
    rows: int
    features: int
    kmax: int
    nnz: int


def csr_to_device(corpus, *, batch_size: Optional[int] = None) -> CSRDevice:
    """Stage a ``repro.data.sparse.CSRCorpus`` (duck-typed) on device.

    ``batch_size`` sizes the one-time tail padding so the CS/SS block
    kernel's whole-batch window stays in bounds without any per-call
    ``jnp.pad`` (an O(nnz) copy otherwise re-run every gradient); without
    it the padding covers the per-row (RS) window and larger block calls
    fall back to padding in the wrapper.
    """
    nnz = int(np.asarray(corpus.indptr[-1]))
    if nnz >= 2 ** 31:
        raise ValueError("CSR corpus too large for int32 element offsets")
    kmax = max(1, int(corpus.kmax))
    K = _round_up(kmax, 128)
    pad = _round_up((batch_size or 1) * kmax + K, 128)

    def flat(mm, dt):
        a = np.zeros(nnz + pad, dt)
        a[:nnz] = np.asarray(mm[:nnz])
        return jnp.asarray(a)

    return CSRDevice(
        vals=flat(corpus.values, np.float32),
        cols=flat(corpus.indices, np.int32),
        indptr=jnp.asarray(np.asarray(corpus.indptr), jnp.int32),
        y=jnp.asarray(np.asarray(corpus.labels), jnp.float32),
        rows=int(corpus.rows), features=int(corpus.features),
        kmax=kmax, nnz=nnz)


def sparse_batch_grad_data(problem: ERMProblem, dev: CSRDevice, w, *,
                           start=None, idx=None, batch_size=None,
                           interpret=None):
    """Fused-CSR equivalent of ``problem.batch_grad_data`` on the densified
    batch.  Pass exactly one of ``start`` (contiguous CS/SS block; needs
    ``batch_size``) or ``idx`` (scattered RS rows)."""
    if (start is None) == (idx is None):
        raise ValueError("pass exactly one of start= (CS/SS) or idx= (RS)")
    nnz = getattr(dev, "nnz", None)
    if start is not None:
        if batch_size is None:
            raise ValueError("start= (CS/SS block) also requires batch_size=")
        return sparse_grad_block(dev.vals, dev.cols, dev.indptr, dev.y, w,
                                 start, loss=problem.loss,
                                 batch_size=batch_size, kmax=dev.kmax,
                                 nnz=nnz, interpret=interpret)
    return sparse_grad_rows(dev.vals, dev.cols, dev.indptr, dev.y, w, idx,
                            loss=problem.loss, kmax=dev.kmax, nnz=nnz,
                            interpret=interpret)


def sparse_batch_grad(problem: ERMProblem, dev: CSRDevice, w, **kw):
    """Fused-CSR equivalent of ``problem.batch_grad`` (adds the l2 term)."""
    return sparse_batch_grad_data(problem, dev, w, **kw) + problem.reg * w


def sparse_batch_margins(dev: CSRDevice, w, *, start=None, idx=None,
                         batch_size=None, interpret=None):
    """Margins of the sampled CSR batch, device-resident end to end — the
    CSR counterpart of ``fused_erm.fused_batch_margins``, ready for a
    step-rule probe once sparse resident mode lands (the streamed CSR
    engine's line search runs on padded-ELL batches via
    ``step_rules.ell_probe``).  Pass exactly one of ``start`` (contiguous
    CS/SS block; needs ``batch_size``) or ``idx`` (scattered RS rows)."""
    if (start is None) == (idx is None):
        raise ValueError("pass exactly one of start= (CS/SS) or idx= (RS)")
    nnz = getattr(dev, "nnz", None)
    if start is not None:
        if batch_size is None:
            raise ValueError("start= (CS/SS block) also requires batch_size=")
        return sparse_margins_block(dev.vals, dev.cols, dev.indptr, w, start,
                                    batch_size=batch_size, kmax=dev.kmax,
                                    nnz=nnz, interpret=interpret)
    return sparse_margins_rows(dev.vals, dev.cols, dev.indptr, w, idx,
                               kmax=dev.kmax, nnz=nnz, interpret=interpret)


def sparse_batch_objective(problem: ERMProblem, dev: CSRDevice, w, *,
                           start=None, idx=None, batch_size=None,
                           interpret=None):
    """Fused-CSR equivalent of ``problem.batch_objective`` on the densified
    batch — margins from the CSR kernel, labels via a cheap O(b) take."""
    from .fused_erm import fused_batch_labels
    z = sparse_batch_margins(dev, w, start=start, idx=idx,
                             batch_size=batch_size, interpret=interpret)
    yb = fused_batch_labels(dev.y, start=start, idx=idx,
                            batch_size=batch_size)
    return (problem.mean_margin_loss(z, yb)
            + 0.5 * problem.reg * jnp.dot(w, w))
