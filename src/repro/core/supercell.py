"""Super-cell execution: one staged data stream drives S experiment cells.

The paper's cost model says an epoch pays ``m * (t_access + t_compute)``;
every solver/step-rule cell of a sweep grid pays the access term again even
when the cells read the SAME corpus under the SAME sampling schedule.  A
**super-cell** groups plan-compatible cells — same data plan: corpus,
format, sampling scheme, seed, batch size, chunk shape, placement — and
drives all of them from ONE staged stream: one read, one ELL/row convert,
one H2D per chunk, then S solver updates against the staged buffer.  The
access and staging cost per cell drops S-fold; the compute term is the
same work the solo runs would have done.

Trajectory contract: every cell's weights are BIT-IDENTICAL to the solo
``execute()`` run of the same plan.  By default every cell runs through
the SOLO engines — the very lru-cached compiled callables ``execute()``
uses — against the shared staged data, so the parity is structural: same
compiled program, same inputs, only the data movement is shared.

``vmap_lanes=True`` additionally batches compute: snapshot-free lanes
(mbsgd, sag, saga) of 2+ cells ride the vmapped engines
(:func:`repro.core.solvers.make_supercell_epoch_fn` /
:func:`make_supercell_resident_fn`), which scan the same ``batch_step``
circuit the solo engines scan with the step size lifted to a traced
per-cell scalar (``step0S``), so cells differing only in step size share
one compiled engine and one device call per chunk.  Batching turns the
per-cell matvecs into cross-cell matmuls, and XLA may tile those with a
different reduction order than the solo matvec — measured drift is ~1e-7
on f32 at 500x64 batches (exact at small shapes, but that is
fusion-dependent, not contractual).  Opt in when sweep throughput
matters more than bit-reproducibility.  Snapshot solvers (svrg, saag2)
always run per cell: their in-scan snapshot-gradient term drifts the
same way once per-cell snapshots diverge.

Grouping has two levels:

* the **super-cell key** (:func:`supercell_key`) — the data plan.  Cells
  in one super-cell share the batch stream, so everything that shapes the
  stream (corpus identity, scheme, seed, batch size, chunk, epoch budget,
  resume point) must match.  Fused-kernel and sharded plans are never
  coalesced (``supercell_key`` returns ``None`` — they fall back solo).
* the **lane key** within a super-cell — the compiled program: solver,
  step mode, line-search shape, loss, regularizer.  Cells in one lane
  differ only in step size; by default each issues its own solo-engine
  call against the shared staged buffer, and under ``vmap_lanes=True``
  an eligible lane collapses to ONE vmapped engine call per chunk.

Accounting: the shared stream is measured once (a private tracer + one
:class:`~repro.data.pipeline.AccessStats`) and attributed to each cell as
``shared / S`` — per-cell ``RunResult.stats``, ``breakdown()`` and span
timelines (every attributed span carries a ``cells=S`` attribute) stay
mutually consistent, so ``verify_timeline()`` holds per cell.  Per-cell
``train_s`` is the amortized epoch wall clock (``wall / S``): summed over
the cells of a super-cell it reproduces the real wall clock.

Checkpoints stay per cell: each cell's ``CheckpointPolicy`` directory gets
the same snapshot schema ``execute()`` writes, so ``resume_from`` on a
cell directory works unchanged and a resumed batch continues exactly
where the uninterrupted solo runs would be.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import ACCESS, COMPUTE, EPOCH, GATHER as GATHER_LANE, H2D, \
    NULL_TRACER, Tracer
from .erm import ERMProblem
from .experiment import (ARRAYS, CSR, FUSED, RESIDENT, ExecutionPlan,
                         RunResult, _EVAL_CHUNK, _RunCheckpointer,
                         _dense_objectives, _objective_jit, _plan_diff,
                         _plan_fingerprint, _put_blocking, _resume_state,
                         _validate_fingerprint, execute)
from .solvers import (SolverConfig, SolverState, epoch_begin, init_state,
                      make_epoch_fn, make_resident_epoch_fn,
                      make_supercell_epoch_fn, make_supercell_resident_fn,
                      streaming_full_grad)
from .step_rules import LINE_SEARCH

#: default cap on cells per super-cell — the vmapped state must fit on the
#: device next to the staged chunk, and the amortization curve flattens
#: past ~8 anyway (access/S is already an 8x cut)
DEFAULT_MAX_CELLS = 8

# step size in the lane key is normalized to this value: cells differing
# only in step size share one compiled engine (the live step rides the
# traced per-cell step0S argument instead)
_STEP_NORM = 1.0


# ---------------------------------------------------------------------------
# coalescing
# ---------------------------------------------------------------------------

def supercell_key(plan_: ExecutionPlan, done0: int = 0) -> Optional[Tuple]:
    """The data-plan identity cells must share to ride one super-cell, or
    ``None`` when the plan is not coalescable (sharded or fused-kernel
    backends keep their solo execution paths).

    ``done0`` is the cell's resume point (0 for a fresh run): cells at
    different points of their batch schedule cannot share a stream.
    """
    s = plan_.spec
    if plan_.shards > 1:
        return None                      # sharded backends stage per-mesh
    if plan_.kernel == FUSED:
        return None                      # fused engines own their DMA
    if plan_.scheme_obj.adaptive:
        # adaptive schemes evolve their own draw stream from run feedback:
        # two cells would diverge after the first observe(), so they can
        # never share a staged stream
        return None
    if s.data.kind == ARRAYS:
        # DataSource equality excludes array payloads; stream identity
        # needs the SAME arrays, so key on object identity like resume does
        data_id: Tuple = ("arrays", id(s.data.X), id(s.data.y))
    else:
        data_id = ("corpus", str(s.data.path))
    return (data_id, plan_.fmt, plan_.backend, plan_.placement,
            plan_.scheme_obj.canonical(), s.seed, s.batch_size, plan_.chunk,
            s.prefetch, plan_.rows, plan_.features, plan_.num_batches,
            plan_.kmax, s.epochs, int(done0))


@dataclasses.dataclass
class CellBatch:
    """One coalesced unit of work: ``plans`` share a :func:`supercell_key`
    (``key is None`` means a solo fallback cell).  ``indices`` are the
    positions of each plan in the submission order, so a caller can map
    results back to requests."""
    key: Optional[Tuple]
    plans: List[ExecutionPlan]
    indices: List[int]

    @property
    def size(self) -> int:
        return len(self.plans)


def coalesce(plans: Sequence[ExecutionPlan], *,
             max_cells: int = DEFAULT_MAX_CELLS,
             done0s: Optional[Sequence[int]] = None) -> List[CellBatch]:
    """Partition plans into :class:`CellBatch` groups.

    Plans with equal :func:`supercell_key` group together (split into
    chunks of at most ``max_cells``); non-coalescable plans become
    singleton batches.  Order: groups appear at their first plan's
    position, so results stream back roughly in submission order.
    """
    if max_cells < 1:
        raise ValueError(f"max_cells must be >= 1 (got {max_cells})")
    done0s = [0] * len(plans) if done0s is None else list(done0s)
    if len(done0s) != len(plans):
        raise ValueError("done0s must align with plans")
    groups: Dict[Tuple, CellBatch] = {}
    out: List[CellBatch] = []
    for i, p in enumerate(plans):
        key = supercell_key(p, done0s[i])
        if key is None:
            out.append(CellBatch(None, [p], [i]))
            continue
        g = groups.get(key)
        if g is None or g.size >= max_cells:
            g = CellBatch(key, [], [])
            groups[key] = g
            out.append(g)
        g.plans.append(p)
        g.indices.append(i)
    return out


def _check_compatible(plans: Sequence[ExecutionPlan],
                      done0s: Sequence[int]) -> None:
    keys = [supercell_key(p, d) for p, d in zip(plans, done0s)]
    if keys[0] is None:
        raise ValueError(
            "plan is not super-cell eligible (sharded or fused backend): "
            + plans[0].backend)
    bad = [f"cell {i}: {plans[i].backend}" if k is None else
           f"cell {i}: data plan differs from cell 0"
           for i, k in enumerate(keys) if k != keys[0]]
    if bad:
        raise ValueError(
            "cells do not share a data plan — coalesce() groups only "
            "compatible specs; differing cells:\n  " + "\n  ".join(bad))


def _check_resume(plan_: ExecutionPlan, resume: RunResult) -> None:
    """The same resume contract ``execute()`` enforces, per cell."""
    if resume.solver_state is None:
        raise ValueError(
            "resume result carries no solver state — reconstruct resumable "
            "state from an on-disk checkpoint via repro.api.resume_from")
    prev, cur = resume.plan.spec.data, plan_.spec.data
    same_arrays = (prev.kind != ARRAYS
                   or (prev.X is cur.X and prev.y is cur.y))
    try:
        _validate_fingerprint(_plan_fingerprint(resume.plan), plan_)
        same_run = True
    except ValueError:
        same_run = False
    if not same_run or not same_arrays:
        diffs = _plan_diff(resume.plan, plan_)
        if not same_arrays:
            diffs.append("spec.data: in-memory sources must be the same "
                         "arrays (X/y object identity)")
        raise ValueError(
            "resume result came from a different plan than its cell:\n  "
            + "\n  ".join(diffs or ["(no field-level difference)"]))


# ---------------------------------------------------------------------------
# per-cell attribution of the shared stream
# ---------------------------------------------------------------------------

def _cell_stats(shared, s_cells: int):
    """The shared stream's :class:`AccessStats`, attributed to one cell:
    time and bytes divide by the cell count (one read served S cells),
    batch/stage counts stay — ``s_per_batch`` then reads as the AMORTIZED
    per-batch access time, which is the quantity the paper's cost model
    multiplies by ``m``."""
    from ..data import pipeline as pipemod
    return pipemod.AccessStats(
        batches=shared.batches,
        access_s=shared.access_s / s_cells,
        bytes_read=shared.bytes_read // s_cells,
        staged=shared.staged,
        h2d_s=shared.h2d_s / s_cells,
        bytes_staged=shared.bytes_staged // s_cells,
        h2d_saved_s=shared.h2d_saved_s / s_cells,
        shards=shared.shards,
        gather_s=shared.gather_s / s_cells)


def _replay_shared_spans(shared: Tracer, tracers: List[Tracer],
                         s_cells: int) -> None:
    """Fan the shared stream's measured spans out to every traced cell at
    ``dur / S``: each cell's access/h2d lanes then sum to exactly its
    attributed stats, so per-cell ``verify_timeline()`` reconciles."""
    live = [t for t in tracers if t.enabled]
    if not live:
        return
    for ev in shared.timeline().events:
        if not ev.toplevel or ev.lane not in (ACCESS, H2D, GATHER_LANE):
            continue
        args = dict(ev.args or {})
        args["cells"] = s_cells
        for t in live:
            # re-anchor: TraceEvent.ts is relative to the SHARED tracer's
            # epoch; event() subtracts the receiving tracer's own epoch
            t.event(ev.name, ev.lane, t0=ev.ts + shared.epoch, dur=ev.dur
                    / s_cells, **args)


def _slice_cell(stateS: SolverState, i: int) -> SolverState:
    return jax.tree_util.tree_map(lambda a: a[i], stateS)


def _stack_states(states: Sequence[SolverState]) -> SolverState:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


#: solvers whose batch step consumes an epoch-level snapshot gradient —
#: vmapping them batches the w/snapshot matvecs across cells, which drifts
#: from the solo reduction order by ulps once snapshots diverge, so their
#: cells run through the SOLO engines against the shared staged data
_SNAPSHOT = ("svrg", "saag2")


class _Lane:
    """One program inside a super-cell: the cells (by batch index) that
    share a solver/step-rule/problem.

    By default every lane keeps per-cell states and calls the solo
    engines — the same lru-cached compiled callables ``execute()`` uses —
    once per cell against the same staged data: compute is not batched,
    but the access amortization is identical and bit-parity is
    structural.  Under ``vmap_lanes=True``, snapshot-free lanes of 2+
    cells (``vmapped``) instead stack their cells' states on a leading
    axis and ride ONE vmapped engine call per staged chunk, with the
    initial step lifted to the traced per-cell ``step0S`` — batched
    matvecs may drift from the solo reduction order by ulps (see the
    module docstring).  Snapshot lanes (svrg/saag2) and single-cell
    lanes always take the solo-engine path.
    """

    def __init__(self, problem: ERMProblem, cfg: SolverConfig,
                 cells: List[int], plans: Sequence[ExecutionPlan],
                 states: Sequence[SolverState], vmap_lanes: bool):
        self.problem = problem
        self.cfg = cfg                    # step size normalized
        self.cells = cells
        self.step_rule = plans[cells[0]].step_rule
        self.vmapped = (vmap_lanes and cfg.solver not in _SNAPSHOT
                        and len(cells) > 1)
        self.cfgs = [plans[i].cfg for i in cells]   # exact per-cell configs
        if self.vmapped:
            self.step0S = jnp.asarray(
                [c.step_size for c in self.cfgs], jnp.float32)
            self.stateS = _stack_states([states[i] for i in cells])
        else:
            self.states = [states[i] for i in cells]

    @property
    def size(self) -> int:
        return len(self.cells)

    def cell_state(self, t: int) -> SolverState:
        return (_slice_cell(self.stateS, t) if self.vmapped
                else self.states[t])

    def cell_w(self, t: int) -> jax.Array:
        return self.stateS.w[t] if self.vmapped else self.states[t].w


def _build_lanes(plans: Sequence[ExecutionPlan],
                 states: Sequence[SolverState],
                 vmap_lanes: bool) -> List[_Lane]:
    order: List[Tuple] = []
    groups: Dict[Tuple, List[int]] = {}
    for i, p in enumerate(plans):
        key = (p.spec.problem, p.cfg._replace(step_size=_STEP_NORM))
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)
    return [_Lane(problem, cfg, groups[(problem, cfg)], plans, states,
                  vmap_lanes)
            for problem, cfg in order]


# ---------------------------------------------------------------------------
# the super-cell executors
# ---------------------------------------------------------------------------

def execute_supercell(plans: Sequence[ExecutionPlan], *,
                      resumes: Optional[Sequence[Optional[RunResult]]] = None,
                      epochs: Optional[int] = None,
                      vmap_lanes: bool = False) -> List[RunResult]:
    """Run S plan-compatible cells off one staged data stream.

    Returns one :class:`RunResult` per plan, in order, each BIT-IDENTICAL
    in trajectory to ``execute(plan, resume=..., epochs=...)`` of the solo
    run, with the shared access/staging cost attributed as ``shared / S``.
    A single-cell call degenerates to exactly the solo path.

    ``vmap_lanes=True`` opts snapshot-free multi-cell lanes into batched
    (vmapped) compute — one engine call per lane per chunk instead of one
    per cell.  Faster for wide lanes, but batched matvecs may drift from
    the solo trajectory by ulps (see the module docstring); leave it off
    when bit-reproducibility matters.
    """
    plans = list(plans)
    if not plans:
        return []
    resumes = list(resumes) if resumes is not None else [None] * len(plans)
    if len(resumes) != len(plans):
        raise ValueError("resumes must align with plans")
    if len(plans) == 1:
        return [execute(plans[0], resume=resumes[0], epochs=epochs)]
    for p, r in zip(plans, resumes):
        if r is not None:
            _check_resume(p, r)
    done0s = [0 if r is None else r.epochs_done for r in resumes]
    _check_compatible(plans, done0s)
    epochs = plans[0].spec.epochs if epochs is None else epochs
    if plans[0].placement == RESIDENT:
        return _supercell_resident(plans, resumes, epochs, vmap_lanes)
    return _supercell_streamed(plans, resumes, epochs, vmap_lanes)


def _cell_tracers(plans: Sequence[ExecutionPlan]) -> List[Tracer]:
    return [p.spec.trace.make_tracer() if p.spec.trace is not None
            else NULL_TRACER for p in plans]


def _shared_tracer(plans: Sequence[ExecutionPlan]) -> Tracer:
    # the shared stream is ALWAYS measured (its spans are the per-cell
    # attribution source); size the ring to the largest cell policy so the
    # replay never undercounts a cell that asked for a bigger buffer
    buf = max([4096] + [p.spec.trace.buffer for p in plans
                        if p.spec.trace is not None])
    return Tracer(enabled=True, buffer=buf)


def _finish_cell(plan_: ExecutionPlan, tracer: Tracer,
                 result: RunResult) -> RunResult:
    if tracer.enabled:
        result.timeline = tracer.timeline()
        pol = plan_.spec.trace
        if pol.path is not None:
            result.timeline.save(pol.path)
    return result


def _supercell_streamed(plans: List[ExecutionPlan],
                        resumes: List[Optional[RunResult]],
                        epochs: int,
                        vmap_lanes: bool = False) -> List[RunResult]:
    from ..data import pipeline as pipemod

    ref = plans[0]
    spec = ref.spec
    S = len(plans)
    m, K, n, b = ref.num_batches, ref.chunk, ref.features, spec.batch_size
    pairs = [_resume_state(p, r) for p, r in zip(plans, resumes)]
    states = [st for st, _ in pairs]
    done0 = pairs[0][1]
    start_step = done0 * m
    lanes = _build_lanes(plans, states, vmap_lanes)
    shared = _shared_tracer(plans)
    tracers = _cell_tracers(plans)

    pcfg = pipemod.PipelineConfig(corpus=spec.data.path, batch_size=b,
                                  sampling=spec.scheme, seed=spec.seed,
                                  prefetch=spec.prefetch)
    if ref.fmt == CSR:
        from ..data import sparse
        csr = sparse.open_csr_corpus(spec.data.path)
        kmax = ref.kmax if ref.kmax else csr.kmax
        pipe = sparse.SparsePipeline(pcfg, start_step=start_step,
                                     tracer=shared)

        def alloc(k):
            return (np.empty((k, b, kmax), np.int32),
                    np.empty((k, b, kmax), np.float32),
                    np.empty((k, b), np.float32))

        def fill(bufs, i, sb):
            bufs[0][i], bufs[1][i], bufs[2][i] = sb.cols, sb.vals, sb.y

        def zeros(k):
            return (jnp.zeros((k, b, kmax), jnp.int32),
                    jnp.zeros((k, b, kmax), jnp.float32),
                    jnp.zeros((k, b), jnp.float32))

        # per-cell objective / snapshot gradients read the CSR corpus
        # directly (the same calls the solo path makes); only the TRAINING
        # stream is shared — eval reads stay untimed in both paths
        def full_grad_at(problem, w, data_term_only=False):
            return jnp.asarray(sparse.csr_full_grad(
                problem, csr, np.asarray(w), data_term_only=data_term_only))

        def eval_cells(ws):
            return [sparse.csr_objective(plans[i].spec.problem, csr,
                                         np.asarray(w)) for i, w in ws]
    else:
        from ..data import dataset
        mm, _ = dataset.open_corpus(spec.data.path)
        pipe = pipemod.DataPipeline(pcfg, start_step=start_step,
                                    tracer=shared)

        def alloc(k):
            return (np.empty((k, b, n), np.float32),
                    np.empty((k, b), np.float32))

        def fill(bufs, i, rows):
            bufs[0][i] = rows[:, :n]
            bufs[1][i] = rows[:, n]

        def zeros(k):
            return (jnp.zeros((k, b, n), jnp.float32),
                    jnp.zeros((k, b), jnp.float32))

        def _row_chunks():
            for lo in range(0, ref.rows, _EVAL_CHUNK):
                rows = np.asarray(mm[lo:lo + _EVAL_CHUNK])
                yield rows[:, :n], rows[:, n]

        def full_grad_at(problem, w, data_term_only=False):
            return streaming_full_grad(problem, w, _row_chunks(),
                                       data_term_only=data_term_only)

        def eval_cells(ws):
            # ONE corpus pass evaluates every recording cell, on the solo
            # driver's blocks and block program, so each value is
            # bit-identical to the solo run's — only the reads are shared
            return _dense_objectives(
                mm, K * b, [(plans[i].spec.problem, w) for i, w in ws])

    # compile every lane against every chunk shape, outside the timers
    shapes = sorted({K, m % K} - {0})
    for lane in lanes:
        if lane.vmapped:
            lane.fn = make_supercell_epoch_fn(lane.problem, lane.cfg)
            for k in shapes:
                dummy = _stack_states([
                    init_state(lane.cfg.solver, jnp.zeros(n, jnp.float32),
                               m) for _ in range(lane.size)])
                js = jnp.zeros((k,), jnp.int32)
                jax.block_until_ready(
                    lane.fn(dummy, *zeros(k), js, lane.step0S).w)
        else:
            # the SOLO engines, per cell: distinct step sizes are distinct
            # (problem, cfg) cache keys, exactly as the solo runs compile
            lane.fns = [make_epoch_fn(lane.problem, c) for c in lane.cfgs]
            for fn in lane.fns:
                for k in shapes:
                    dummy = init_state(lane.cfg.solver,
                                       jnp.zeros(n, jnp.float32), m)
                    js = jnp.zeros((k,), jnp.int32)
                    jax.block_until_ready(fn(dummy, *zeros(k), js).w)
            data_only = lane.cfg.solver == "saag2"
            jax.block_until_ready(full_grad_at(
                lane.problem, jnp.zeros(n, jnp.float32),
                data_term_only=data_only))

    def refresh_lane(lane: _Lane) -> None:
        """Per-cell snapshot refresh — the same host-driven full-gradient
        stream the solo path runs, one cell at a time."""
        if lane.vmapped:
            return
        data_only = lane.cfg.solver == "saag2"
        lane.states = [
            epoch_begin(lane.problem, lane.cfgs[t], st,
                        lambda w: full_grad_at(lane.problem, w,
                                               data_term_only=data_only))
            for t, st in enumerate(lane.states)]

    def host_chunks():
        it = iter(pipe)
        step, total = start_step, start_step + m * epochs
        while step < total:
            j0 = step % m
            k = min(K, m - j0)
            bufs = alloc(k)
            for i in range(k):
                fill(bufs, i, next(it))
            yield bufs + (j0,)
            step += k

    def convert(arg):
        *bufs, j0 = arg
        js = (np.arange(j0, j0 + bufs[0].shape[0]) % m).astype(np.int32)
        return tuple(bufs) + (js,)

    stager = pipemod.DeviceStager(host_chunks(), put=_put_blocking,
                                  convert=convert, depth=2,
                                  stats=pipe.stats, tracer=shared)
    chunks_iter = iter(stager)

    prefixes = [[] if r is None else [float(h) for h in r.history]
                for r in resumes]
    histories: List[List[float]] = [[] for _ in plans]
    rcks = [_RunCheckpointer(p, done0, epochs, tracers[i])
            for i, p in enumerate(plans)]
    compute_s = [0.0] * S
    train_s = 0.0

    try:
        for e in range(epochs):
            with shared.timespan("train_epoch", EPOCH, epoch=done0 + e,
                                 cells=S) as se:
                for lane in lanes:
                    refresh_lane(lane)
                done = 0
                while done < m:
                    args = next(chunks_iter)
                    k = int(args[0].shape[0])
                    for lane in lanes:
                        if lane.vmapped:
                            with shared.timespan("chunk", COMPUTE,
                                                 epoch=done0 + e,
                                                 first_batch=done,
                                                 step_rule=lane.step_rule,
                                                 cells=lane.size) as sc:
                                lane.stateS = lane.fn(lane.stateS, *args,
                                                      lane.step0S)
                                jax.block_until_ready(lane.stateS.w)
                                sc.set(batches=k)
                            for i in lane.cells:
                                compute_s[i] += sc.dur / lane.size
                                tracers[i].event(
                                    "chunk", COMPUTE, t0=sc.t0,
                                    dur=sc.dur / lane.size,
                                    epoch=done0 + e, first_batch=done,
                                    batches=k, step_rule=lane.step_rule,
                                    cells=lane.size)
                        else:
                            # solo engines, per cell, on the SAME staged
                            # chunk — each cell's compute is its own
                            for t, i in enumerate(lane.cells):
                                with shared.timespan(
                                        "chunk", COMPUTE, epoch=done0 + e,
                                        first_batch=done,
                                        step_rule=lane.step_rule,
                                        cells=1) as sc:
                                    lane.states[t] = lane.fns[t](
                                        lane.states[t], *args)
                                    jax.block_until_ready(
                                        lane.states[t].w)
                                    sc.set(batches=k)
                                compute_s[i] += sc.dur
                                tracers[i].event(
                                    "chunk", COMPUTE, t0=sc.t0,
                                    dur=sc.dur, epoch=done0 + e,
                                    first_batch=done, batches=k,
                                    step_rule=lane.step_rule, cells=1)
                    done += k
            train_s += se.dur
            for i in range(S):
                tracers[i].event("train_epoch", EPOCH, t0=se.t0,
                                 dur=se.dur / S, epoch=done0 + e, cells=S)
            # per-epoch probes and checkpoints: untimed, like the solo loop
            recording = [(i, _cell_w(lanes, i)) for i in range(S)
                         if plans[i].spec.record_objective]
            if recording:
                vals = eval_cells(recording)
                for (i, _), v in zip(recording, vals):
                    histories[i].append(float(v))
            for lane in lanes:
                for t, i in enumerate(lane.cells):
                    rcks[i].after_epoch(
                        e, lane.cell_state(t),
                        {"scheme": ref.scheme_name, "seed": spec.seed,
                         "step": start_step + m * (e + 1)},
                        prefixes[i] + histories[i], _cell_stats(pipe.stats,
                                                                S))
    finally:
        for rck in rcks:
            rck.finish()
        stager.close()
        pipe.close()

    _replay_shared_spans(shared, tracers, S)
    results: List[RunResult] = []
    cell_lane = {i: lane for lane in lanes for i in lane.cells}
    final_eval: List[Tuple[int, jax.Array]] = [
        (i, _cell_w(lanes, i)) for i in range(S) if not histories[i]]
    final_vals = dict(zip([i for i, _ in final_eval],
                          eval_cells(final_eval) if final_eval else []))
    for i, p in enumerate(plans):
        lane = cell_lane[i]
        st = lane.cell_state(lane.cells.index(i))
        if p.cfg.step_mode == LINE_SEARCH:
            tracers[i].metrics.counter("ls.invocations").inc(m * epochs)
        objective = (histories[i][-1] if histories[i]
                     else float(final_vals[i]))
        res = RunResult(
            plan=p, objective=objective,
            history=np.asarray(prefixes[i] + histories[i]),
            w=np.asarray(st.w), solver_state=st,
            sampler_state={"scheme": ref.scheme_name, "seed": spec.seed,
                           "step": start_step + m * epochs},
            epochs_run=epochs, epochs_done=done0 + epochs,
            stats=_cell_stats(pipe.stats, S),
            train_s=train_s / S, compute_s=compute_s[i])
        results.append(_finish_cell(p, tracers[i], res))
    return results


def _cell_w(lanes: List[_Lane], i: int) -> jax.Array:
    for lane in lanes:
        if i in lane.cells:
            return lane.cell_w(lane.cells.index(i))
    raise KeyError(i)


def _supercell_resident(plans: List[ExecutionPlan],
                        resumes: List[Optional[RunResult]],
                        epochs: int,
                        vmap_lanes: bool = False) -> List[RunResult]:
    from ..data import pipeline as pipemod

    ref = plans[0]
    spec = ref.spec
    S = len(plans)
    n = ref.features
    shared = _shared_tracer(plans)
    tracers = _cell_tracers(plans)
    stats = pipemod.AccessStats()
    h2d_dt = 0.0

    if spec.data.kind == ARRAYS:
        # in-memory source: no read, no booked staging — same as solo
        X = jnp.asarray(spec.data.X, jnp.float32)
        y = jnp.asarray(spec.data.y, jnp.float32)
    else:
        pipe = pipemod.DataPipeline(pipemod.PipelineConfig(
            corpus=spec.data.path, batch_size=spec.batch_size,
            sampling=spec.scheme, seed=spec.seed, prefetch=0, resident=True),
            tracer=shared)
        stats = pipe.stats
        rows = pipe.read_all()
        Xh = np.ascontiguousarray(rows[:, :n])
        yh = np.ascontiguousarray(rows[:, n])
        with shared.timespan("stage_resident", H2D,
                             bytes=Xh.nbytes + yh.nbytes) as sp:
            # lint: allow[REPRO002] the accounted staging site: the span IS
            # the measurement record_h2d books below
            X, y = jax.block_until_ready((jax.device_put(Xh),
                                          jax.device_put(yh)))
        h2d_dt = sp.dur
        stats.record_h2d(h2d_dt, Xh.nbytes + yh.nbytes)

    pairs = [_resume_state(p, r) for p, r in zip(plans, resumes)]
    states = [st for st, _ in pairs]
    done0 = pairs[0][1]
    lanes = _build_lanes(plans, states, vmap_lanes)
    fresh = all(r is None for r in resumes)
    for lane in lanes:
        if lane.vmapped:
            lane.fn = make_supercell_resident_fn(
                lane.problem, lane.cfg, ref.scheme_name, spec.batch_size)
        else:
            # solo resident engines, per cell: snapshot refresh stays
            # in-graph exactly as the solo run compiles it
            lane.fns = [make_resident_epoch_fn(lane.problem, c,
                                               ref.scheme_name, spec.batch_size)
                        for c in lane.cfgs]
        if fresh:
            if lane.vmapped:
                dummy = _stack_states([
                    init_state(lane.cfg.solver, jnp.zeros(n, jnp.float32),
                               ref.num_batches) for _ in range(lane.size)])
                jax.block_until_ready(
                    lane.fn(dummy, X, y, jax.random.PRNGKey(1),
                            lane.step0S).w)
            else:
                for fn in lane.fns:
                    dummy = init_state(lane.cfg.solver,
                                       jnp.zeros(n, jnp.float32),
                                       ref.num_batches)
                    jax.block_until_ready(
                        fn(dummy, X, y, jax.random.PRNGKey(1)).w)
            jax.block_until_ready(
                _objective_jit(lane.problem, lane.cell_w(0), X, y))

    # shared key schedule: every cell sees the epoch keys its solo run
    # would have drawn (same seed is part of the super-cell key)
    key = jax.random.PRNGKey(spec.seed)
    for _ in range(done0):
        key, _ = jax.random.split(key)

    prefixes = [[] if r is None else [float(h) for h in r.history]
                for r in resumes]
    histories: List[List[float]] = [[] for _ in plans]
    rcks = [_RunCheckpointer(p, done0, epochs, tracers[i])
            for i, p in enumerate(plans)]
    compute_s = [0.0] * S
    train_s = 0.0

    try:
        for e in range(epochs):
            key, sub = jax.random.split(key)
            with shared.timespan("epoch", EPOCH, epoch=done0 + e,
                                 cells=S) as se:
                for lane in lanes:
                    if lane.vmapped:
                        with shared.timespan("resident_epoch", COMPUTE,
                                             epoch=done0 + e,
                                             step_rule=lane.step_rule,
                                             cells=lane.size) as sc:
                            lane.stateS = lane.fn(lane.stateS, X, y, sub,
                                                  lane.step0S)
                            jax.block_until_ready(lane.stateS.w)
                        for i in lane.cells:
                            compute_s[i] += sc.dur / lane.size
                            tracers[i].event("resident_epoch", COMPUTE,
                                             t0=sc.t0,
                                             dur=sc.dur / lane.size,
                                             epoch=done0 + e,
                                             step_rule=lane.step_rule,
                                             cells=lane.size)
                    else:
                        for t, i in enumerate(lane.cells):
                            with shared.timespan("resident_epoch", COMPUTE,
                                                 epoch=done0 + e,
                                                 step_rule=lane.step_rule,
                                                 cells=1) as sc:
                                lane.states[t] = lane.fns[t](
                                    lane.states[t], X, y, sub)
                                jax.block_until_ready(lane.states[t].w)
                            compute_s[i] += sc.dur
                            tracers[i].event("resident_epoch", COMPUTE,
                                             t0=sc.t0, dur=sc.dur,
                                             epoch=done0 + e,
                                             step_rule=lane.step_rule,
                                             cells=1)
            train_s += se.dur
            for i in range(S):
                tracers[i].event("epoch", EPOCH, t0=se.t0, dur=se.dur / S,
                                 epoch=done0 + e, cells=S)
            if spec.data.kind != ARRAYS and e > 0:
                stats.record_h2d_saved(h2d_dt)
            for lane in lanes:
                if lane.cfg.step_mode == LINE_SEARCH:
                    for i in lane.cells:
                        tracers[i].metrics.counter("ls.invocations").inc(
                            ref.num_batches)
                for t, i in enumerate(lane.cells):
                    if plans[i].spec.record_objective:
                        histories[i].append(float(_objective_jit(
                            lane.problem, lane.cell_w(t), X, y)))
                    rcks[i].after_epoch(
                        e, lane.cell_state(t),
                        {"scheme": ref.scheme_name, "seed": spec.seed,
                         "epochs": done0 + e + 1},
                        prefixes[i] + histories[i], _cell_stats(stats, S))
    finally:
        for rck in rcks:
            rck.finish()

    _replay_shared_spans(shared, tracers, S)
    results: List[Tuple[int, RunResult]] = []
    for lane in lanes:
        for t, i in enumerate(lane.cells):
            p = plans[i]
            st = lane.cell_state(t)
            objective = (histories[i][-1] if histories[i]
                         else float(_objective_jit(lane.problem, st.w, X,
                                                   y)))
            res = RunResult(
                plan=p, objective=objective,
                history=np.asarray(prefixes[i] + histories[i]),
                w=np.asarray(st.w), solver_state=st,
                sampler_state={"scheme": ref.scheme_name, "seed": spec.seed,
                               "epochs": done0 + epochs},
                epochs_run=epochs, epochs_done=done0 + epochs,
                stats=_cell_stats(stats, S),
                train_s=train_s / S, compute_s=compute_s[i])
            results.append((i, _finish_cell(p, tracers[i], res)))
    results.sort(key=lambda pair: pair[0])
    return [r for _, r in results]
