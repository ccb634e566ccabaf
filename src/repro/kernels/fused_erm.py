"""Fused sampled-gather + ERM gradient kernels — the epoch engine's hot path.

The reference path materializes the mini-batch in HBM before the gradient
kernel ever sees it: ``gather_batch``/``dynamic_slice`` writes (b, n) rows
out, then ``ERMProblem.batch_grad`` reads them back.  These kernels fuse the
two: the sampled rows are DMA'd straight into VMEM and the data-term
gradient

    g_data = (1/b) * Xb^T s,   s_i = dloss/dz(z_i, y_i),   z = Xb w

comes out the other side without the batch ever existing as an HBM array.
Both of the paper's access patterns (§2) keep their structural signature:

* :func:`fused_grad_block` (CS/SS): one contiguous block DMA per feature
  tile.  A two-phase grid computes the margins z across feature tiles
  (phase 0) and the per-feature-tile gradient contraction Xb^T s (phase 1)
  entirely in VMEM; with one feature tile the block is read once.
* :func:`fused_grad_rows` (RS): a grid of b steps, one row DMA each — the
  per-row descriptor cost that makes RS slow is preserved at the kernel
  level, the batch materialization is not.

TPU layout rules shape both.  A float32 array in HBM is tiled (8, 128), and
Mosaic only accepts a block that starts on a tile boundary, so:

* the block kernel reads the 8-aligned window of ``_window(b)`` rows that
  contains the batch, and masks the rows outside ``[start', start'+b)``;
* the rows kernel reads the aligned 8-row group that holds each sampled row
  and selects the row with a sublane mask.

Pallas issues and double-buffers these DMAs from the BlockSpecs.

Both need the row count to be a multiple of 8 and at least ``_window(b)``
(:func:`kernel_rows`); a corpus that is not is zero-padded inside the call,
an O(l n) copy per call, so the planner only picks these kernels for a
corpus that needs no padding.  The margin and gradient reductions run on the
VPU in float32: at n=28 a matrix unit pass would be mostly padding, and the
result does not depend on the matmul precision.

Semantics contract (tested in ``tests/test_fused_erm.py``):

* block: rows ``[start', start'+b)`` with ``start' = clip(start, 0, l-b)`` —
  identical clamping to ``lax.dynamic_slice``/``erm.slice_batch``, so the
  fused path is interchangeable with the reference CS/SS path including the
  overlapping last batch when ``l % b != 0``.
* rows: exactly the rows of ``idx`` (wrap-around indices from
  ``samplers.epoch_indices`` included), matching ``gather_batch``.

Alongside the gradients, :func:`fused_margins_block` / :func:`fused_margins_rows`
expose the margin pass ``z = Xb @ w`` stand-alone (phase 0 of the block
kernel, the row dot of the rows kernel): this is the line-search
trial-objective surface — ``repro.core.step_rules.fused_probe`` evaluates a
whole Armijo trial ladder from two margin sweeps, keeping line search
device-resident on the fused backends.

``interpret=None`` runs the Pallas interpreter on the CPU backend and the
compiled kernel everywhere else.  The interpreter checks the kernels'
arithmetic, not whether Mosaic accepts them: ``tests/test_tpu_compile.py``
compiles them for a described v5e chip.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.erm import ERMProblem, LOGISTIC, SMOOTH_HINGE, SQUARE

LOSSES = (LOGISTIC, SQUARE, SMOOTH_HINGE)

# feature tiles wider than this are split (VMEM budget: b * tile_n floats)
_MAX_TILE_N = 1024
_LANES = 128
_SUBLANES = 8      # float32 rows per HBM tile: DMA row offsets align to this


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> interpret on the CPU backend only; a TPU compiles."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def _dloss(loss: str, z: jax.Array, y: jax.Array) -> jax.Array:
    """d/dz of the per-example margin loss (matches erm._margin_losses)."""
    if loss == LOGISTIC:
        # d/dz log(1+exp(-yz)) = -y * sigmoid(-yz)
        return -y * jax.nn.sigmoid(-y * z)
    if loss == SQUARE:
        return z - y
    if loss == SMOOTH_HINGE:
        t = y * z
        return -y * jnp.where(t >= 1.0, 0.0, jnp.where(t <= 0.0, 1.0, 1.0 - t))
    raise ValueError(f"unknown loss {loss!r}")


def _feature_tile(n: int) -> int:
    """n itself up to _MAX_TILE_N, else the largest multiple of 128 in
    [128, _MAX_TILE_N] dividing n, else n (single tile).

    A tile narrower than n starts at a lane offset, which Mosaic accepts
    only at multiples of 128; divisibility keeps every tile DMA full-size.
    """
    if n <= _MAX_TILE_N:
        return n
    for tile in range(_MAX_TILE_N, _LANES - 1, -_LANES):
        if n % tile == 0:
            return tile
    return n


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _window(b: int) -> int:
    """Rows of the 8-aligned window that holds any b consecutive rows."""
    return _round_up(b + _SUBLANES - 1, _SUBLANES)


def kernel_rows(rows: int, batch_size: int) -> int:
    """Row count the kernels read without padding: ``rows`` itself when it
    is a multiple of 8 and holds one block window, else larger."""
    return max(_round_up(rows, _SUBLANES), _window(batch_size))


def _row_group(n: int) -> Tuple[int, int]:
    """The block one grid step of the rows kernels DMAs: the aligned 8-row
    group that holds the sampled row."""
    return (_SUBLANES, n)


def row_group_bytes(n: int) -> int:
    """HBM bytes of one :func:`_row_group` DMA, lanes padded to 128."""
    rows, cols = _row_group(n)
    return rows * _round_up(cols, _LANES) * 4


def _pad_rows(X: jax.Array, y: jax.Array, batch_size: int):
    """Zero rows up to :func:`kernel_rows` (a copy; none when aligned)."""
    l = X.shape[0]
    pad = kernel_rows(l, batch_size) - l
    if pad == 0:
        return X, y
    return (jnp.pad(X, ((0, pad), (0, 0))),
            None if y is None else jnp.pad(y, (0, pad)))


def _tile_cols(t, tn: int, n: int):
    """Lane index of feature tile t into a (1, n) ref: none when one tile
    spans n."""
    if tn == n:
        return (slice(None),)
    return (slice(None), pl.ds(pl.multiple_of(t * tn, _LANES), tn))


def _window_spec(bw: int, tn: int, n: int, t_axis: int):
    """X's (bw, tn) block: rows from the 8-aligned window start in scalar
    prefetch, lanes from feature tile ``t_axis`` of the grid.  Pallas
    pipelines the DMA and skips it when consecutive steps read the same
    block (the two phases of a one-tile grid read X once)."""
    return pl.BlockSpec(
        (pl.Element(bw), pl.Element(tn)),
        lambda *ix: (pl.multiple_of(ix[-1][0], _SUBLANES),
                     0 if tn == n else pl.multiple_of(ix[t_axis] * tn,
                                                      _LANES)))


def _block_window(X, y, start, batch_size: int):
    """(padded X, aligned window start + offset of the batch in it, labels
    of the window as a (bw, 1) column)."""
    l = X.shape[0]
    b, bw = batch_size, _window(batch_size)
    if b > l:
        raise ValueError(f"batch_size {b} > rows {l}")
    X, y = _pad_rows(X, y, b)
    # clamp BOTH ends like lax.dynamic_slice (negative starts go to 0)
    start = jnp.clip(start.astype(jnp.int32), 0, l - b)
    base = jnp.minimum(start // _SUBLANES * _SUBLANES, X.shape[0] - bw)
    sc = jnp.stack([base, start - base])
    yw = (None if y is None else
          jax.lax.dynamic_slice(y, (base,), (bw,)).reshape(bw, 1))
    return X, sc, yw


def _row_mask(sc_ref, b: int, bw: int) -> jax.Array:
    """(bw, 1) mask of the window rows that belong to the batch."""
    r = jax.lax.broadcasted_iota(jnp.int32, (bw, 1), 0) - sc_ref[1]
    return (r >= 0) & (r < b)


# ---------------------------------------------------------------------------
# CS/SS: one contiguous block, two-phase feature-tiled grid
# ---------------------------------------------------------------------------

def _block_kernel(loss: str, b: int, bw: int, tn: int, n: int,
                  sc_ref, x_ref, y_ref, w_ref, g_ref, z_ref, s_ref):
    p = pl.program_id(0)   # 0: accumulate z across tiles, 1: emit gradient
    t = pl.program_id(1)   # feature tile
    cols = _tile_cols(t, tn, n)

    @pl.when((p == 0) & (t == 0))
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    @pl.when(p == 0)
    def _():
        z_ref[...] += jnp.sum(x_ref[...] * w_ref[cols], axis=1,
                              keepdims=True)

    @pl.when((p == 1) & (t == 0))
    def _():
        s = _dloss(loss, z_ref[...], y_ref[...]) / b
        s_ref[...] = jnp.where(_row_mask(sc_ref, b, bw), s, 0.0)

    @pl.when(p == 1)
    def _():
        g_ref[cols] = jnp.sum(s_ref[...] * x_ref[...], axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("loss", "batch_size", "interpret"))
def fused_grad_block(X: jax.Array, y: jax.Array, w: jax.Array,
                     start: jax.Array, *, loss: str, batch_size: int,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Data-term gradient of the contiguous batch starting at row ``start``.

    X: (l, n), y: (l,), w: (n,), start: scalar int32 row start (clamped to
    ``l - batch_size`` like ``dynamic_slice``).  Returns (n,) float32:
    (1/b) Xb^T dloss(Xb w, yb) — no regularizer (see :func:`fused_batch_grad`).
    """
    n = X.shape[1]
    b, bw, tn = batch_size, _window(batch_size), _feature_tile(n)
    X, sc, yw = _block_window(X.astype(jnp.float32), y.astype(jnp.float32),
                              start, b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(2, n // tn),
        in_specs=[_window_spec(bw, tn, n, 1),              # X window
                  pl.BlockSpec(memory_space=pltpu.VMEM),   # window labels
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # w (1, n)
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((bw, 1), jnp.float32),  # z accumulator
                        pltpu.VMEM((bw, 1), jnp.float32)], # s = dloss/b
    )
    g = pl.pallas_call(
        functools.partial(_block_kernel, loss, b, bw, tn, n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(sc, X, yw, w.reshape(1, n).astype(jnp.float32))
    return g.reshape(n).astype(w.dtype)


# ---------------------------------------------------------------------------
# RS: per-row DMA grid, gradient accumulated across grid steps
# ---------------------------------------------------------------------------

def _row_of_group(idx_ref, x_ref) -> jax.Array:
    """The sampled row of this step's aligned (8, n) group, other rows 0."""
    r = idx_ref[pl.program_id(0)] % _SUBLANES
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0)
    return jnp.where(sub == r, x_ref[...], 0.0)


def _row_dot(row, w_ref) -> jax.Array:
    """(1, 1) margin of a masked (8, n) group against w."""
    return jnp.sum(jnp.sum(row * w_ref[...], axis=1, keepdims=True),
                   axis=0, keepdims=True)


def _rows_kernel(loss: str, b: int, idx_ref, x_ref, w_ref, y_ref, g_ref):
    i = pl.program_id(0)   # one sampled row per grid step

    @pl.when(i == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    row = _row_of_group(idx_ref, x_ref)
    s = _dloss(loss, _row_dot(row, w_ref), y_ref[i]) / b
    g_ref[...] += jnp.sum(s * row, axis=0, keepdims=True)


def _rows_grid_spec(idx, n: int, out_block, extra_in=()):
    """Grid of b steps; step i DMAs the 8-row group holding row idx[i]."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(idx.shape[0],),
        in_specs=[pl.BlockSpec(
                      _row_group(n),
                      lambda i, idx_ref: (idx_ref[i] // _SUBLANES, 0)),
                  pl.BlockSpec((1, n), lambda i, idx_ref: (0, 0)),
                  *extra_in],
        out_specs=pl.BlockSpec(out_block, lambda i, idx_ref: (0, 0)),
    )


@functools.partial(jax.jit, static_argnames=("loss", "interpret"))
def fused_grad_rows(X: jax.Array, y: jax.Array, w: jax.Array,
                    idx: jax.Array, *, loss: str,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Data-term gradient of the scattered batch ``X[idx]`` (RS pattern).

    X: (l, n), y: (l,), w: (n,), idx: (b,) int32 row ids.  Grid of b steps,
    one row-group DMA each — the kernel-level expression of RS's per-element
    seek cost.  Returns (n,) float32 data gradient.
    """
    n = X.shape[1]
    b = idx.shape[0]
    idx = idx.astype(jnp.int32)
    yb = jnp.take(y.astype(jnp.float32), idx)      # O(b) label gather
    X, _ = _pad_rows(X.astype(jnp.float32), None, b)
    g = pl.pallas_call(
        functools.partial(_rows_kernel, loss, b),
        grid_spec=_rows_grid_spec(
            idx, n, (1, n), [pl.BlockSpec(memory_space=pltpu.SMEM)]),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(idx, X, w.reshape(1, n).astype(jnp.float32), yb)
    return g.reshape(n).astype(w.dtype)


# ---------------------------------------------------------------------------
# batch margins: z = Xb @ w without materializing the batch — the line-search
# trial-objective kernel (phase 0 of the gradient kernels, stand-alone)
# ---------------------------------------------------------------------------

def _block_margins_kernel(tn: int, n: int, sc_ref, x_ref, w_ref, z_ref):
    t = pl.program_id(0)   # feature tile

    @pl.when(t == 0)
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    z_ref[...] += jnp.sum(x_ref[...] * w_ref[_tile_cols(t, tn, n)], axis=1,
                          keepdims=True)


@functools.partial(jax.jit, static_argnames=("batch_size", "interpret"))
def fused_margins_block(X: jax.Array, w: jax.Array, start: jax.Array, *,
                        batch_size: int,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Margins ``z = Xb @ w`` of the contiguous batch at row ``start``
    (CS/SS), with the same ``min(start, l-b)`` clamping as
    :func:`fused_grad_block`.  Returns (b,) float32."""
    n = X.shape[1]
    b, bw, tn = batch_size, _window(batch_size), _feature_tile(n)
    X, sc, _ = _block_window(X.astype(jnp.float32), None, start, b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tn,),
        in_specs=[_window_spec(bw, tn, n, 0),              # X window
                  pl.BlockSpec(memory_space=pltpu.VMEM)],  # w (1, n)
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )
    z = pl.pallas_call(
        functools.partial(_block_margins_kernel, tn, n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bw, 1), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(sc, X, w.reshape(1, n).astype(jnp.float32))
    return jax.lax.dynamic_slice(z.reshape(bw), (sc[1],), (b,)).astype(w.dtype)


def _rows_margins_kernel(b: int, idx_ref, x_ref, w_ref, z_ref):
    i = pl.program_id(0)   # one sampled row per grid step

    @pl.when(i == 0)
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    z = _row_dot(_row_of_group(idx_ref, x_ref), w_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    z_ref[...] = jnp.where(lane == i, z, z_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_margins_rows(X: jax.Array, w: jax.Array, idx: jax.Array, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Margins ``z_i = X[idx[i]] . w`` of a scattered batch (RS): a grid of
    b steps, one row-group DMA each, like :func:`fused_grad_rows`.
    Returns (b,) float32."""
    n = X.shape[1]
    b = idx.shape[0]
    X, _ = _pad_rows(X.astype(jnp.float32), None, b)
    z = pl.pallas_call(
        functools.partial(_rows_margins_kernel, b),
        grid_spec=_rows_grid_spec(idx, n, (1, b)),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(idx.astype(jnp.int32), X, w.reshape(1, n).astype(jnp.float32))
    return z.reshape(b).astype(w.dtype)


def fused_batch_margins(X, w, *, start=None, idx=None, batch_size=None,
                        interpret=None):
    """Margins of the sampled batch, device-resident end to end.

    Pass exactly one of ``start`` (contiguous CS/SS block; needs
    ``batch_size``) or ``idx`` (scattered RS rows).  This is what the
    step-rule subsystem's ``fused_probe`` evaluates: a full trial-ladder
    line search costs TWO margin sweeps (``z(w)``, ``z(v)``), not one
    objective pass per trial step.
    """
    if (start is None) == (idx is None):
        raise ValueError("pass exactly one of start= (CS/SS) or idx= (RS)")
    if start is not None:
        if batch_size is None:
            raise ValueError("start= (CS/SS block) also requires batch_size=")
        return fused_margins_block(X, w, start, batch_size=batch_size,
                                   interpret=interpret)
    return fused_margins_rows(X, w, idx, interpret=interpret)


def fused_batch_labels(y, *, start=None, idx=None, batch_size=None):
    """Labels of the sampled batch, with the SAME ``clip(start, 0, l-b)``
    clamping / wrap-around ``take`` semantics as the margin and gradient
    kernels — the one place that logic lives, so label extraction can
    never drift from what the kernels actually read."""
    if start is not None:
        start_c = jnp.clip(start.astype(jnp.int32), 0,
                           y.shape[0] - batch_size)
        return jax.lax.dynamic_slice(y, (start_c,), (batch_size,))
    return jnp.take(y, idx.astype(jnp.int32))


def fused_batch_objective(problem: ERMProblem, X, y, w, *, start=None,
                          idx=None, batch_size=None, interpret=None):
    """Fused equivalent of ``problem.batch_objective(w, *gather(...))`` —
    margins from the fused kernel, labels via a cheap O(b) slice/take."""
    z = fused_batch_margins(X, w, start=start, idx=idx,
                            batch_size=batch_size, interpret=interpret)
    yb = fused_batch_labels(y, start=start, idx=idx, batch_size=batch_size)
    return (problem.mean_margin_loss(z, yb)
            + 0.5 * problem.reg * jnp.dot(w, w))


# ---------------------------------------------------------------------------
# solver-facing wrappers (parity contract with the reference gather path)
# ---------------------------------------------------------------------------

def fused_batch_grad_data(problem: ERMProblem, X, y, w, *, start=None,
                          idx=None, batch_size=None, interpret=None):
    """Fused equivalent of ``problem.batch_grad_data(w, *gather(...))``.

    Pass exactly one of ``start`` (contiguous CS/SS block; needs
    ``batch_size``) or ``idx`` (scattered RS rows).
    """
    if (start is None) == (idx is None):
        raise ValueError("pass exactly one of start= (CS/SS) or idx= (RS)")
    if start is not None:
        if batch_size is None:
            raise ValueError("start= (CS/SS block) also requires batch_size=")
        return fused_grad_block(X, y, w, start, loss=problem.loss,
                                batch_size=batch_size, interpret=interpret)
    return fused_grad_rows(X, y, w, idx, loss=problem.loss,
                           interpret=interpret)


def fused_batch_grad(problem: ERMProblem, X, y, w, **kw):
    """Fused equivalent of ``problem.batch_grad`` (adds the l2 term)."""
    return fused_batch_grad_data(problem, X, y, w, **kw) + problem.reg * w
