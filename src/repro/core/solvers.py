"""The paper's five stochastic solvers, each usable with RS/CS/SS sampling
and with constant step size or backtracking line search (paper §4.1).

Solvers (step 7 of Algorithm 1):

* **MBSGD**   w <- w - (a/|B|) sum_{i in B} grad f_i(w)                 [23]
* **SAG**     table of per-batch gradients; w <- w - a * mean(table)    [22]
* **SAGA**    w <- w - a (g_B - table_B + mean(table))                  [11]
* **SVRG**    epoch snapshot wt, mu = full grad(wt);
              w <- w - a (g_B(w) - g_B(wt) + mu)                        [13]
* **SAAG-II** like SVRG but the snapshot is the previous epoch's LAST
              iterate and the l2 regularizer is applied exactly at every
              step (biased variance reduction)                          [3]

Execution backends — INTERNAL to the planner.  Callers declare an
``ExperimentSpec`` and go through :func:`repro.core.experiment.plan` /
``execute``; the planner selects among these entry points (they are no
longer exported from ``repro.core``):

* :func:`run` — fully jit'd device-resident loop (``lax.scan`` over batches,
  Python loop over epochs). Batch selection happens IN-GRAPH with the paper's
  access patterns: ``dynamic_slice`` for CS/SS (one DMA descriptor) vs row
  gather for RS (~b descriptors).
* :func:`make_step_fn` / :func:`make_epoch_fn` / :func:`epoch_begin` — jit'd
  updates for host-driven loops where batches stream from a memmapped corpus
  (``repro.data``); this is the paper's actual regime (data on disk) and is
  what ``benchmarks/erm_timing.py`` times.  ``make_epoch_fn`` is the chunked
  epoch engine: ONE device call scans K staged batches with donated solver
  state, amortizing per-batch Python dispatch K-fold.
* :func:`make_resident_epoch_fn` — fused host mode: the whole corpus staged
  on device once, epochs driven in-graph.

Set ``SolverConfig(use_fused=True)`` to route device-resident gradients
through the fused Pallas kernels (``repro.kernels.fused_erm``): the sampled
rows are DMA'd straight into VMEM and the batch never materializes in HBM.
The reference gather path stays the default and is the parity oracle.

Step determination is delegated to :mod:`repro.core.step_rules`
(ConstantStep / BacktrackingLS / VectorizedLS): every solver builds a
``BatchProbe`` for its batch representation (dense, padded-ELL, or fused
margins kernels) and asks the config's rule to pick the step — which is
what lets line search run on EVERY backend, including the fused
device-resident path.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import samplers, step_rules
from .erm import ERMProblem, gather_batch
from .step_rules import CONSTANT, LINE_SEARCH, SEQUENTIAL, VECTORIZED  # noqa: F401 — re-exported vocabulary

MBSGD, SAG, SAGA, SVRG, SAAG2 = "mbsgd", "sag", "saga", "svrg", "saag2"
SOLVERS = (MBSGD, SAG, SAGA, SVRG, SAAG2)


class SolverConfig(NamedTuple):
    solver: str = MBSGD
    step_mode: str = CONSTANT
    step_size: float = 0.1        # constant step, or initial step for LS
    ls_shrink: float = 0.5        # backtracking factor rho
    ls_c: float = 1e-4            # Armijo constant
    ls_max_iter: int = 25
    use_fused: bool = False       # fused gather+grad Pallas kernels
    sparse: bool = False          # CSR corpus: padded-ELL batches, no densify
    ls_mode: str = VECTORIZED     # trial-ladder sweep | "sequential" ref


class SolverState(NamedTuple):
    """Uniform state pytree; unused slots are zero-size arrays."""
    w: jax.Array
    table: jax.Array          # (m, n) per-batch gradient memory (SAG/SAGA)
    table_mean: jax.Array     # (n,) running mean of table        (SAG/SAGA)
    snapshot: jax.Array       # (n,) epoch snapshot w~            (SVRG/SAAG2)
    snapshot_grad: jax.Array  # (n,) full gradient at snapshot    (SVRG/SAAG2)


def _needs_table(solver: str) -> bool:
    return solver in (SAG, SAGA)


def _needs_snapshot(solver: str) -> bool:
    return solver in (SVRG, SAAG2)


def batch_access(scheme: str) -> str:
    """How a resident epoch of ``scheme`` reads its batches: ``"block"``,
    one contiguous block per batch (CS/SS; the fused ``fused_grad_block``),
    or ``"rows"``, one read per sampled row (RS; ``fused_grad_rows``)."""
    return "block" if scheme in (samplers.CYCLIC,
                                 samplers.SYSTEMATIC) else "rows"


def fused_row_dmas(cfg: SolverConfig, num_batches: int, batch_size: int,
                   n: int) -> Tuple[int, int]:
    """(row DMAs, bytes) of one resident RS epoch's ``fused_grad_rows``
    calls in :func:`fused_batch_step`: a grid of ``batch_size`` steps per
    call, one call per batch and a second at the snapshot for
    SVRG/SAAG-II, each step one aligned row group.  Line-search margin
    sweeps are not counted."""
    from ..kernels import fused_erm  # deferred: keep core import pallas-free
    calls = 2 if _needs_snapshot(cfg.solver) else 1
    dmas = num_batches * batch_size * calls
    return dmas, dmas * fused_erm.row_group_bytes(n)


def init_state(solver: str, w0: jax.Array, num_batches: int) -> SolverState:
    n = w0.shape[0]
    dt = w0.dtype
    # NOTE: each slot gets its OWN buffer (no shared zero-size array) so the
    # state pytree is donation-safe in make_epoch_fn — XLA rejects donating
    # one buffer twice.
    table = jnp.zeros((num_batches, n), dt) if _needs_table(solver) else jnp.zeros((0, 0), dt)
    tmean = jnp.zeros((n,) if _needs_table(solver) else (0,), dt)
    snap = jnp.zeros((n,) if _needs_snapshot(solver) else (0,), dt)
    sgrad = jnp.zeros((n,) if _needs_snapshot(solver) else (0,), dt)
    return SolverState(w0, table, tmean, snap, sgrad)


# ---------------------------------------------------------------------------
# step size selection — delegated to the repro.core.step_rules subsystem
# ---------------------------------------------------------------------------

def _step_rule(cfg: SolverConfig) -> step_rules.StepRule:
    """Resolve the config's step rule (ConstantStep / BacktrackingLS /
    VectorizedLS) — every solver and every execution backend picks its step
    through this one dispatch, with the batch presented as a
    :class:`~repro.core.step_rules.BatchProbe`."""
    return step_rules.from_config(cfg)


# ---------------------------------------------------------------------------
# one mini-batch update (shared by both execution modes)
# ---------------------------------------------------------------------------

def _solver_direction(problem: ERMProblem, cfg: SolverConfig,
                      state: SolverState, j: jax.Array, gd: jax.Array,
                      gd_snap: Optional[jax.Array],
                      ) -> Tuple[jax.Array, jax.Array, SolverState]:
    """(v, g, new_state) from precomputed DATA-term gradients.

    ``gd = (1/b) Xb^T dloss(Xb w, yb)`` at ``state.w`` and ``gd_snap`` the
    same at ``state.snapshot`` (only for snapshot solvers).  Factoring the
    update rules over data gradients is what lets the fused kernels and the
    reference gather path share one implementation: the full batch gradient
    is just ``gd + reg * w``.
    """
    w = state.w
    g = gd + problem.reg * w
    solver = cfg.solver

    if solver == MBSGD:
        v = g
        new_state = state

    elif solver == SAG:
        m = state.table.shape[0]
        old = state.table[j]
        mean_new = state.table_mean + (g - old) / m
        v = mean_new
        new_state = state._replace(table=state.table.at[j].set(g),
                                   table_mean=mean_new)

    elif solver == SAGA:
        m = state.table.shape[0]
        old = state.table[j]
        v = g - old + state.table_mean
        mean_new = state.table_mean + (g - old) / m
        new_state = state._replace(table=state.table.at[j].set(g),
                                   table_mean=mean_new)

    elif solver == SVRG:
        g_snap = gd_snap + problem.reg * state.snapshot
        v = g - g_snap + state.snapshot_grad
        new_state = state

    elif solver == SAAG2:
        # data-term variance reduction + EXACT regularizer gradient
        v = gd - gd_snap + state.snapshot_grad + problem.reg * w
        new_state = state

    else:
        raise ValueError(f"unknown solver {solver!r}")

    return v, g, new_state


def batch_step(problem: ERMProblem, cfg: SolverConfig, state: SolverState,
               Xb: jax.Array, yb: jax.Array, j: jax.Array,
               weight: Optional[jax.Array] = None) -> SolverState:
    """Apply one solver update using batch ``j`` with data (Xb, yb).

    ``weight`` (optional traced scalar) rescales the batch-mean data
    gradient — the unbiasedness correction the weighted schemes
    (``BatchIndices.weight``) emit: for importance sampling it is
    ``1/(m p_j)``, for stochastic batch size ``b / b_t`` (zero-padded rows
    contribute zero to ``X^T dloss``, so the padded mean only needs
    re-normalizing).  ``None`` keeps the uniform program byte-for-byte."""
    w = state.w
    gd = problem.batch_grad_data(w, Xb, yb)
    gd_snap = (problem.batch_grad_data(state.snapshot, Xb, yb)
               if _needs_snapshot(cfg.solver) else None)
    if weight is not None:
        gd = gd * weight
        gd_snap = None if gd_snap is None else gd_snap * weight
    v, g, new_state = _solver_direction(problem, cfg, state, j, gd, gd_snap)
    alpha = _step_rule(cfg).pick(step_rules.dense_probe(problem, Xb, yb),
                                 w, v, g)
    return new_state._replace(w=w - alpha * v)


def sparse_batch_step(problem: ERMProblem, cfg: SolverConfig,
                      state: SolverState, cols: jax.Array, vals: jax.Array,
                      yb: jax.Array, j: jax.Array,
                      weight: Optional[jax.Array] = None) -> SolverState:
    """One solver update from a padded-ELL CSR batch — the corpus is never
    densified.  (cols, vals): (b, kmax) per ``repro.data.sparse.SparseBatch``;
    the update rules are shared with the dense path via
    :func:`_solver_direction`, and line search backtracks on the sparse
    batch objective.  ``weight`` as in :func:`batch_step`."""
    w = state.w
    gd = problem.ell_batch_grad_data(w, cols, vals, yb)
    gd_snap = (problem.ell_batch_grad_data(state.snapshot, cols, vals, yb)
               if _needs_snapshot(cfg.solver) else None)
    if weight is not None:
        gd = gd * weight
        gd_snap = None if gd_snap is None else gd_snap * weight
    v, g, new_state = _solver_direction(problem, cfg, state, j, gd, gd_snap)
    alpha = _step_rule(cfg).pick(
        step_rules.ell_probe(problem, cols, vals, yb), w, v, g)
    return new_state._replace(w=w - alpha * v)


def fused_batch_step(problem: ERMProblem, cfg: SolverConfig,
                     state: SolverState, X: jax.Array, y: jax.Array,
                     j: jax.Array, *, start: Optional[jax.Array] = None,
                     idx: Optional[jax.Array] = None,
                     batch_size: Optional[int] = None) -> SolverState:
    """One solver update whose gradients come from the fused Pallas kernels.

    The mini-batch is described by ``start`` (CS/SS contiguous block) or
    ``idx`` (RS rows) and never materializes in HBM.  Line search stays
    device-resident too: trial objectives come from the fused margin
    kernels through :func:`step_rules.fused_probe` (two margin sweeps per
    vectorized ladder, one per trial for the sequential reference).
    """
    from ..kernels import fused_erm  # deferred: keep core import pallas-free

    kw = (dict(start=start, batch_size=batch_size) if start is not None
          else dict(idx=idx))
    gd = fused_erm.fused_batch_grad_data(problem, X, y, state.w, **kw)
    gd_snap = (fused_erm.fused_batch_grad_data(problem, X, y, state.snapshot,
                                               **kw)
               if _needs_snapshot(cfg.solver) else None)
    v, g, new_state = _solver_direction(problem, cfg, state, j, gd, gd_snap)
    rule = _step_rule(cfg)
    probe = (step_rules.fused_probe(problem, X, y, **kw)
             if rule.needs_probe else None)
    alpha = rule.pick(probe, state.w, v, g)
    return new_state._replace(w=state.w - alpha * v)


def epoch_begin(problem: ERMProblem, cfg: SolverConfig, state: SolverState,
                full_grad_at: Callable[[jax.Array], jax.Array]) -> SolverState:
    """Refresh epoch-level memory. ``full_grad_at`` computes the full (or
    data-term, for SAAG-II) gradient — injected so host mode can stream it."""
    if not _needs_snapshot(cfg.solver):
        return state
    # copy, don't alias: snapshot sharing w's buffer would make the state
    # pytree un-donatable (XLA rejects donating one buffer twice)
    return state._replace(snapshot=jnp.array(state.w),
                          snapshot_grad=full_grad_at(state.w))


# ---------------------------------------------------------------------------
# device-resident jit'd runner
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("problem", "cfg", "scheme", "batch_size",
                                   "rows"))
def _run_one_epoch(problem: ERMProblem, cfg: SolverConfig, scheme: str,
                   batch_size: int, state: SolverState, X: jax.Array,
                   y: jax.Array, key: jax.Array,
                   rows: Optional[int] = None) -> SolverState:
    # ``rows`` (static) is the TRUE corpus length when X/y carry zero-row
    # padding (the sharded 'psum' placement pads so the corpus shards evenly
    # across the mesh).  The sampler schedule runs over ``rows``; block
    # starts are clamped to the true extent (matching the implicit
    # dynamic_slice clamp an unpadded corpus gets) and the snapshot
    # full-gradient masks the pad rows.  ``rows=None`` keeps the original
    # program byte-for-byte — the bit-parity surface of the sharded
    # 'gather' mode.
    padded = rows is not None and rows != X.shape[0]
    l = rows if rows is not None else X.shape[0]
    m = samplers.num_batches(l, batch_size)

    if _needs_snapshot(cfg.solver):
        data_only = cfg.solver == SAAG2
        if padded:
            fg = lambda w: problem.masked_full_grad(w, X, y, l,
                                                    data_term_only=data_only)
        elif data_only:
            fg = lambda w: problem.batch_grad_data(w, X, y)
        else:
            fg = lambda w: problem.full_grad(w, X, y)
        state = epoch_begin(problem, cfg, state, fg)

    contiguous = batch_access(scheme) == "block"
    if contiguous:
        starts = samplers.batch_slice_starts(scheme, key, l, batch_size)
        if padded:
            # the implicit dynamic_slice clamp now sits at the PADDED end;
            # clamp to the true extent so the trailing batch reads the same
            # rows an unpadded corpus would
            starts = jnp.minimum(starts, l - batch_size)
    else:
        idx_mat = samplers.epoch_indices(scheme, key, l, batch_size)

    def body(st, j):
        if contiguous:
            if cfg.use_fused:
                # fused gather+grad: one block DMA, batch never hits HBM
                return fused_batch_step(problem, cfg, st, X, y, j,
                                        start=starts[j],
                                        batch_size=batch_size), None
            # ONE contiguous block read per batch (CS/SS access pattern).
            Xb = jax.lax.dynamic_slice(X, (starts[j], 0), (batch_size, X.shape[1]))
            yb = jax.lax.dynamic_slice(y, (starts[j],), (batch_size,))
        else:
            if cfg.use_fused:
                # fused per-row DMA grid (RS access pattern)
                return fused_batch_step(problem, cfg, st, X, y, j,
                                        idx=idx_mat[j]), None
            # scattered row gather (RS access pattern)
            Xb, yb = gather_batch(X, y, idx_mat[j])
        return batch_step(problem, cfg, st, Xb, yb, j), None

    # NO unroll here, unlike make_epoch_fn: the resident loop is the
    # ls-mode parity surface (tests pin seq == vec trajectories bit-exact),
    # and unrolling one mode but not the other changes XLA fusion enough
    # to shift shared arithmetic by ulps
    state, _ = jax.lax.scan(body, state, jnp.arange(m))
    return state


def run(problem: ERMProblem, cfg: SolverConfig, scheme: str, X: jax.Array,
        y: jax.Array, w0: jax.Array, *, batch_size: int, epochs: int,
        seed: int = 0, record_objective: bool = True,
        ) -> Tuple[jax.Array, jnp.ndarray]:
    """Run `epochs` epochs; returns (w, per-epoch objective history)."""
    if cfg.sparse:
        raise ValueError(
            "run() is the dense device-resident loop; CSR corpora go through "
            "make_epoch_fn (host-driven padded-ELL chunks) or the "
            "repro.kernels.sparse_erm fused kernels")
    l = X.shape[0]
    m = samplers.num_batches(l, batch_size)
    state = init_state(cfg.solver, w0, m)
    key = jax.random.PRNGKey(seed)
    hist = []
    obj = jax.jit(lambda w: problem.objective(w, X, y))
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        state = _run_one_epoch(problem, cfg, scheme, batch_size, state, X, y, sub)
        if record_objective:
            hist.append(obj(state.w))
    history = jnp.stack(hist) if hist else jnp.zeros((0,), X.dtype)
    return state.w, history


# ---------------------------------------------------------------------------
# host-driven mode (memmapped data; the paper's actual regime)
# ---------------------------------------------------------------------------

def make_step_fn(problem: ERMProblem, cfg: SolverConfig):
    """jit'd per-batch update for host loops that stream batches.

    Dense: ``(state, Xb, yb, j) -> state``.  With ``cfg.sparse``:
    ``(state, cols, vals, yb, j) -> state`` on padded-ELL CSR batches.
    """
    if cfg.use_fused:
        # the per-batch host step consumes an already-materialized batch;
        # silently ignoring the flag here used to misreport what ran —
        # the planner (repro.core.experiment.plan) rejects the combo with
        # the same message before execution ever starts
        raise ValueError(
            "use_fused applies to the device-resident epoch runners: "
            "make_step_fn consumes materialized batches, which leaves "
            "nothing to fuse")
    if cfg.sparse:
        @jax.jit
        def sparse_step(state: SolverState, cols: jax.Array, vals: jax.Array,
                        yb: jax.Array, j: jax.Array) -> SolverState:
            return sparse_batch_step(problem, cfg, state, cols, vals, yb, j)
        return sparse_step

    @jax.jit
    def step(state: SolverState, Xb: jax.Array, yb: jax.Array,
             j: jax.Array) -> SolverState:
        return batch_step(problem, cfg, state, Xb, yb, j)
    return step


@lru_cache(maxsize=32)   # bounded: step_size is data-dependent (1/L per corpus)
def make_epoch_fn(problem: ERMProblem, cfg: SolverConfig,
                  weighted: bool = False):
    """Chunked epoch engine: jit'd (state, Xc, yc, js) -> state.

    ``Xc: (K, b, n)``, ``yc: (K, b)``, ``js: (K,)`` are K staged mini-batches
    scanned in ONE device call — per-batch Python dispatch, H2D launch and
    jit-call overhead are amortized K-fold, which is what lets the paper's
    access-pattern signal show above interpreter noise in the benchmark.

    With ``cfg.sparse`` the chunk is padded-ELL CSR and the signature becomes
    ``(state, colsc, valsc, yc, js)`` with ``colsc: (K, b, kmax) int32``,
    ``valsc: (K, b, kmax) float32`` — the corpus is never densified; compute
    per batch is O(b * kmax), not O(b * n).

    With ``weighted=True`` (the adaptive Scheme path) the signature gains a
    trailing per-batch weight vector ``ws: (K,) float32`` — the scheme's
    unbiasedness correction, threaded into :func:`batch_step` as a traced
    scalar; the unweighted program stays byte-for-byte untouched.

    ``state`` is donated: the caller must treat the passed-in state as
    consumed and rebind the return value.  Identical (problem, cfg) pairs
    share one compiled callable via a bounded lru_cache, so re-entering
    the benchmark loop never re-traces; distinct chunk sizes K are just
    new shape specializations of the same cached function.
    """
    if cfg.use_fused:
        raise ValueError(
            "use_fused applies to the device-resident run(): the chunked "
            "host engine consumes staged batches, which are materialized "
            "by construction — there is nothing left to fuse")
    # unrolling trims per-iteration loop overhead for cheap straight-line
    # bodies — constant step AND the vectorized trial-ladder line search;
    # only the sequential reference keeps a data-dependent while_loop per
    # batch, where unrolling just bloats compile time
    sequential_ls = (cfg.step_mode == LINE_SEARCH
                     and cfg.ls_mode == SEQUENTIAL)
    unroll = 1 if sequential_ls else 8

    if cfg.sparse:
        if weighted:
            @partial(jax.jit, donate_argnums=(0,))
            def sparse_epoch_chunk_w(state: SolverState, colsc: jax.Array,
                                     valsc: jax.Array, yc: jax.Array,
                                     js: jax.Array,
                                     ws: jax.Array) -> SolverState:
                def body(st, inp):
                    cols, vals, yb, j, w = inp
                    return sparse_batch_step(problem, cfg, st, cols, vals,
                                             yb, j, weight=w), None
                out, _ = jax.lax.scan(body, state,
                                      (colsc, valsc, yc, js, ws),
                                      unroll=unroll)
                return out
            return sparse_epoch_chunk_w

        @partial(jax.jit, donate_argnums=(0,))
        def sparse_epoch_chunk(state: SolverState, colsc: jax.Array,
                               valsc: jax.Array, yc: jax.Array,
                               js: jax.Array) -> SolverState:
            def body(st, inp):
                cols, vals, yb, j = inp
                return sparse_batch_step(problem, cfg, st, cols, vals,
                                         yb, j), None
            out, _ = jax.lax.scan(body, state, (colsc, valsc, yc, js),
                                  unroll=unroll)
            return out
        return sparse_epoch_chunk

    if weighted:
        @partial(jax.jit, donate_argnums=(0,))
        def epoch_chunk_w(state: SolverState, Xc: jax.Array, yc: jax.Array,
                          js: jax.Array, ws: jax.Array) -> SolverState:
            def body(st, inp):
                Xb, yb, j, w = inp
                return batch_step(problem, cfg, st, Xb, yb, j,
                                  weight=w), None
            out, _ = jax.lax.scan(body, state, (Xc, yc, js, ws),
                                  unroll=unroll)
            return out
        return epoch_chunk_w

    @partial(jax.jit, donate_argnums=(0,))
    def epoch_chunk(state: SolverState, Xc: jax.Array, yc: jax.Array,
                    js: jax.Array) -> SolverState:
        def body(st, inp):
            Xb, yb, j = inp
            return batch_step(problem, cfg, st, Xb, yb, j), None
        out, _ = jax.lax.scan(body, state, (Xc, yc, js), unroll=unroll)
        return out
    return epoch_chunk


# the benchmark's fault harness (bench/harness/faults.py) clears this cache
# under its former name; every super-cell cell runs make_epoch_fn
make_supercell_epoch_fn = make_epoch_fn


def make_resident_epoch_fn(problem: ERMProblem, cfg: SolverConfig,
                           scheme: str, batch_size: int,
                           rows: Optional[int] = None):
    """Fused host mode: ``(state, X, y, key) -> state`` with the WHOLE corpus
    resident on device (``PipelineConfig.resident``).

    Batch selection happens in-graph — ``batch_slice_starts`` drives one
    ``dynamic_slice`` per CS/SS batch, ``epoch_indices`` one gather per RS
    batch — so after the one-time staging there is no per-chunk H2D at all;
    the driver credits the avoided restaging via
    ``AccessStats.record_h2d_saved``.  Snapshot solvers refresh their full
    gradient in the same device call.

    ``rows`` is the true corpus length when the staged arrays are zero-row
    padded (the sharded 'psum' placement); see :func:`_run_one_epoch`.
    """
    if cfg.sparse:
        raise ValueError(
            "resident mode stages a dense (l, n) corpus; CSR corpora keep "
            "the host-driven sparse epoch engine")
    if rows is not None and cfg.use_fused:
        raise ValueError(
            "use_fused samples with the kernels' own end-of-corpus clamping, "
            "which a padded (sharded 'psum') corpus would defeat — the "
            "planner keeps sharded placements on the eager engines")
    return partial(_run_one_epoch, problem, cfg, scheme, batch_size,
                   rows=rows)


def streaming_full_grad(problem: ERMProblem, w, batch_iter, *, data_term_only=False):
    """Full gradient accumulated over streamed (Xb, yb, weight) batches."""
    gfun = problem.batch_grad_data if data_term_only else problem.batch_grad
    acc = jnp.zeros_like(w)
    total = 0
    for Xb, yb in batch_iter:
        acc = acc + gfun(w, jnp.asarray(Xb), jnp.asarray(yb)) * Xb.shape[0]
        total += Xb.shape[0]
    return acc / total


def theoretical_rate(alpha: float, mu: float) -> float:
    """Per-epoch contraction factor (1 - 2*alpha*mu) from Theorem 1."""
    return 1.0 - 2.0 * alpha * mu


def error_floor(alpha: float, L: float, mu: float, R0: float) -> float:
    """Asymptotic suboptimality bound L*alpha*R0^2 / (4 mu) from Theorem 1."""
    return L * alpha * R0 ** 2 / (4.0 * mu)
