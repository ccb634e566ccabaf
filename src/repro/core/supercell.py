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

This module only groups cells.  The placement's driver in
:mod:`repro.core.experiment` runs them: a solo ``execute()`` is that
driver's one-cell case, and :func:`execute_supercell` hands it S cells.

Trajectory contract: every cell's weights are BIT-IDENTICAL to the solo
``execute()`` run of the same plan.  Every cell runs through the solo
engines — the very lru-cached compiled callables ``execute()`` uses —
against the shared staged data, so the parity is structural: same
compiled program, same inputs, only the data movement is shared.

The **super-cell key** (:func:`supercell_key`) is the data plan.  Cells in
one super-cell share the batch stream, so everything that shapes the
stream (corpus identity, scheme, seed, batch size, chunk, epoch budget,
resume point) must match.  Fused-kernel, sharded and adaptive plans are
never coalesced (``supercell_key`` returns ``None`` — they run solo).

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

from ..obs import ACCESS, EPOCH, GATHER as GATHER_LANE, H2D, NULL_TRACER, \
    Tracer
from .experiment import (ARRAYS, FUSED, ExecutionPlan, RunResult,
                         _attach_timeline, _validate_resume, _drive, execute)

#: default cap on cells per super-cell — every cell's state must fit on the
#: device next to the staged chunk, and the amortization curve flattens
#: past ~8 anyway (access/S is already an 8x cut)
DEFAULT_MAX_CELLS = 8


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


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def execute_supercell(plans: Sequence[ExecutionPlan], *,
                      resumes: Optional[Sequence[Optional[RunResult]]] = None,
                      epochs: Optional[int] = None) -> List[RunResult]:
    """Run S plan-compatible cells off one staged data stream.

    Returns one :class:`RunResult` per plan, in order, each BIT-IDENTICAL
    in trajectory to ``execute(plan, resume=..., epochs=...)`` of the solo
    run, with the shared access/staging cost attributed as ``shared / S``.
    A single-cell call is exactly the solo run.
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
            _validate_resume(p, r)
    _check_compatible(plans, [0 if r is None else r.epochs_done
                              for r in resumes])
    epochs = plans[0].spec.epochs if epochs is None else epochs
    # the shared stream is ALWAYS measured (its spans are the per-cell
    # attribution source); size the ring to the largest cell policy so the
    # replay never undercounts a cell that asked for a bigger buffer
    shared = Tracer(enabled=True, buffer=max(
        [4096] + [p.spec.trace.buffer for p in plans
                  if p.spec.trace is not None]))
    tracers = [p.spec.trace.make_tracer() if p.spec.trace is not None
               else NULL_TRACER for p in plans]
    results = _drive(plans, resumes, epochs, shared, tracers)
    _replay_shared_spans(shared, tracers, len(plans))
    return [_attach_timeline(p, t, r)
            for p, t, r in zip(plans, tracers, results)]


def _replay_shared_spans(shared: Tracer, tracers: List[Tracer],
                         s_cells: int) -> None:
    """Fan the shared stream's measured spans out to every traced cell at
    ``dur / S``: each cell's access/h2d lanes then sum to exactly its
    attributed stats, so per-cell ``verify_timeline()`` reconciles, and
    its epoch lane sums to its attributed ``train_s``."""
    live = [t for t in tracers if t.enabled]
    if not live:
        return
    for ev in shared.timeline().events:
        if not ev.toplevel or ev.lane not in (ACCESS, H2D, GATHER_LANE,
                                              EPOCH):
            continue
        args = dict(ev.args or {})
        args["cells"] = s_cells
        for t in live:
            # re-anchor: TraceEvent.ts is relative to the SHARED tracer's
            # epoch; event() subtracts the receiving tracer's own epoch
            t.event(ev.name, ev.lane, t0=ev.ts + shared.epoch, dur=ev.dur
                    / s_cells, **args)
