"""Unified ExperimentSpec → plan → run API over all execution paths.

The paper's claim is a matrix — 5 solvers × {RS, CS, SS} sampling ×
{constant, line-search} steps — and the epoch engines multiplied it by
dense/CSR corpora, streamed/resident placement, and fused/eager kernels.
Before this module every caller hand-wired its own combination of
``SolverConfig`` flags and the four solver entry points.  Now there is one
declarative surface:

    spec = ExperimentSpec(data=DataSource.corpus("corpus.bin"),
                          solver="saga", scheme="systematic", epochs=5)
    result = execute(plan(spec))          # or run_experiment(spec)

* :class:`ExperimentSpec` — a frozen description of WHAT to run: problem
  (loss, reg), data source, sampling scheme, solver, step rule, and budget
  (batch size, epochs, seed).  No execution detail leaks in; the overrides
  (``placement``, ``kernel``, ``chunk``) default to ``"auto"``.
* :func:`plan` — lowers a spec into an explicit :class:`ExecutionPlan`:
  streamed vs resident (corpus bytes vs device memory), dense vs CSR,
  fused vs eager kernels, single-host vs sharded data-parallel (a
  ``mesh`` with >1 batch-axis devices selects the sharded backends, with
  ``reduction='gather'`` — bit-identical, access-sharded — or ``'psum'``
  — compute-sharded), and the chunked epoch shape.  Invalid combinations
  fail HERE with a :class:`PlanError` naming the conflict — never
  silently fall back at run time.  The chosen backend and every
  decision's reason are recorded on the plan (``plan.why``,
  ``plan.describe()``).
* :func:`execute` — runs a plan and returns a uniform :class:`RunResult`:
  convergence trace, :class:`~repro.data.pipeline.AccessStats`, wall-clock
  breakdown, and resumable sampler/solver state.  ``execute(plan,
  resume=prev)`` continues a run exactly where a previous result stopped
  (same batch schedule a single uninterrupted run would have used).

The four solver entry points (``run`` / ``make_step_fn`` /
``make_epoch_fn`` / ``make_resident_epoch_fn`` in
:mod:`repro.core.solvers`) are internal backends selected by the planner;
``benchmarks/erm_timing.py`` and the examples go through this module only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
from functools import partial
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..checkpoint.checkpointer import (Checkpointer, CheckpointPolicy,
                                       atomic_write_text)
from ..distributed.sharding import data_parallel_width, make_staging_put
from ..obs import (ACCESS, COMPUTE, DRIVER, EPOCH, GATHER as GATHER_LANE,
                   H2D, NULL_TRACER, Timeline, TracePolicy, Tracer)
from . import samplers, schemes
from .erm import ERMProblem, LOGISTIC, SMOOTH_HINGE, SQUARE
from .solvers import (CONSTANT, LINE_SEARCH, SOLVERS, SolverConfig,
                      SolverState, batch_access, epoch_begin,
                      fused_row_dmas, init_state, make_epoch_fn,
                      make_resident_epoch_fn, streaming_full_grad)
from .step_rules import LS_MODES, VECTORIZED, validate_ls

LOSSES = (LOGISTIC, SQUARE, SMOOTH_HINGE)

# ---- spec-level knobs ------------------------------------------------------
AUTO = "auto"
STREAMED, RESIDENT = "streamed", "resident"     # placement
FUSED, EAGER = "fused", "eager"                 # kernel
GATHER, PSUM = "gather", "psum"                 # sharded reduction mode

# ---- data source kinds -----------------------------------------------------
ARRAYS, DENSE, CSR = "arrays", "dense", "csr"

# ---- backends the planner can select ---------------------------------------
STREAMED_EAGER = "streamed-eager"    # DataPipeline + chunked epoch engine
SPARSE_CSR = "sparse-csr"            # SparsePipeline + sparse chunked engine
RESIDENT_EAGER = "resident-eager"    # in-graph epochs, gather/dynamic_slice
RESIDENT_FUSED = "resident-fused"    # in-graph epochs, fused Pallas kernels
RESIDENT_SPARSE = "resident-sparse"  # CSR staged whole as padded ELL
SHARDED_STREAMED = "sharded-streamed"  # chunks sharded across a device mesh
SHARDED_RESIDENT = "sharded-resident"  # corpus sharded across a device mesh
BACKENDS = (STREAMED_EAGER, SPARSE_CSR, RESIDENT_EAGER, RESIDENT_FUSED,
            RESIDENT_SPARSE, SHARDED_STREAMED, SHARDED_RESIDENT)

# resident-placement budget on the CPU backend, which reports no memory
# stats: stage corpora up to this size, stream anything larger
DEFAULT_RESIDENT_BUDGET = 1 << 30
# the TPU tiles an array's minor dimension in lanes of this width: a
# resident (rows, kmax) ELL array holds kmax rounded up to it
_LANES = 128
# per staged chunk when spec.chunk is unset (matches the benchmark's
# historical default)
_CHUNK_BYTE_BUDGET = 64 << 20
_STEP_SAMPLE_ROWS = 4096       # rows sampled for the auto 1/L step size
_EVAL_CHUNK = 8192             # rows per snapshot-gradient/block-loss chunk


class PlanError(ValueError):
    """A spec combination that cannot execute — raised by :func:`plan` with
    the reason, instead of a silent fallback at run time."""


# ---------------------------------------------------------------------------
# data sources
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DataSource:
    """Where the training data lives.

    Use the constructors: :meth:`arrays` for in-memory ``(X, y)`` (device-
    resident by construction), :meth:`corpus` for an on-disk corpus — a
    dense memmap (``dataset.write_corpus``/``synth_erm_corpus``) or a CSR
    directory (``sparse.write_csr_corpus``/``synth_sparse_classification``),
    sniffed by layout.  The array payload is excluded from equality so specs
    stay hashable/comparable.
    """
    kind: str                                   # ARRAYS | DENSE | CSR
    path: Optional[Path] = None
    X: Optional[object] = dataclasses.field(default=None, compare=False,
                                            repr=False)
    y: Optional[object] = dataclasses.field(default=None, compare=False,
                                            repr=False)

    @staticmethod
    def arrays(X, y) -> "DataSource":
        if getattr(X, "ndim", None) != 2 or X.shape[0] != len(y):
            raise PlanError("DataSource.arrays wants X: (l, n) with y: (l,)")
        return DataSource(ARRAYS, X=X, y=y)

    @staticmethod
    def corpus(path) -> "DataSource":
        path = Path(path)
        if (path / "meta.json").exists():           # CSR corpus directory
            return DataSource(CSR, path=path)
        return DataSource(DENSE, path=path)


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """Frozen description of one experiment: problem + data + scheme +
    solver + step rule + budget.

    The last block (``placement`` / ``kernel`` / ``chunk`` / ``prefetch`` /
    ``resident_budget``) overrides planner decisions; the defaults let
    :func:`plan` choose from the data's size and format.
    """
    data: DataSource
    # problem
    loss: str = LOGISTIC
    reg: float = 1e-4
    # method
    solver: str = "mbsgd"
    # a Scheme instance or a legacy string ("random"/"cyclic"/"systematic");
    # strings resolve to the canonical objects via schemes.resolve, and the
    # describe()/to_json/fingerprint surfaces all record the canonical
    # scheme.name + params either way
    scheme: Union[str, schemes.Scheme] = samplers.SYSTEMATIC
    step_mode: str = CONSTANT
    step_size: Optional[float] = None   # None → 1/L (constant) or 1.0 (LS)
    # line-search hyperparameters (step_mode="line_search")
    ls_mode: str = AUTO                 # AUTO | SEQUENTIAL | VECTORIZED
    ls_shrink: float = 0.5              # backtracking factor rho, in (0, 1)
    ls_c: float = 1e-4                  # Armijo constant, in (0, 1)
    ls_max_iter: int = 25               # trial-ladder length
    # budget
    batch_size: int = 500
    epochs: int = 3
    seed: int = 0
    record_objective: bool = True       # per-epoch trace (final obj always)
    # execution overrides (AUTO lets the planner decide)
    placement: str = AUTO               # AUTO | STREAMED | RESIDENT
    kernel: str = AUTO                  # AUTO | FUSED | EAGER
    chunk: Optional[int] = None         # batches per device call (streamed)
    prefetch: int = 2                   # pipeline read-ahead (streamed)
    resident_budget: Optional[int] = None   # bytes; None → device stats
    # data-parallel placement: a mesh with >1 batch-axis devices lowers to
    # the sharded backends (sharded-streamed / sharded-resident); a 1-device
    # mesh (or None) keeps the single-host backends.  ``reduction`` picks how
    # per-device work combines: 'gather' (default) stages chunks sharded —
    # per-device H2D drops by the mesh width — then reshards to replicated
    # at the jit boundary, so trajectories are BIT-IDENTICAL to the
    # single-host backends; 'psum' keeps chunks sharded through the epoch
    # scan (compute and memory per device drop too) with GSPMD combining
    # partial gradients — deterministic per mesh, but reduction order
    # differs from the single-host circuit by ulps.
    mesh: Optional[Mesh] = None
    reduction: str = AUTO               # AUTO | GATHER | PSUM
    # durability: a CheckpointPolicy makes execute() snapshot the full run
    # state (solver pytree + sampler (seed, step) + AccessStats + objective
    # trace) every `policy.every` cumulative epochs, asynchronously — the
    # epoch loop never waits on the disk write.  repro.api.resume_from(dir)
    # reconstructs a resumable RunResult after a crash, including ELASTIC
    # restore of a 'gather'-mode sharded run onto a different mesh width.
    checkpoint: Optional[CheckpointPolicy] = None
    # observability: a TracePolicy makes execute() record span timelines
    # (access / h2d / compute / checkpoint / gather lanes) + a metrics
    # registry into RunResult.timeline, exportable as Chrome/Perfetto trace
    # JSON via RunResult.save_trace (or automatically to policy.path).
    # Deliberately EXCLUDED from the plan fingerprint: tracing never
    # changes what a run computes, so a checkpointed run may resume with
    # tracing toggled either way.
    trace: Optional[TracePolicy] = None

    @property
    def problem(self) -> ERMProblem:
        return ERMProblem(loss=self.loss, reg=self.reg)


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Explicit lowering of a spec: which backend runs, with what shapes.

    Everything a reader needs to know what WILL happen is here before
    anything executes — the selected backend, the resolved
    :class:`SolverConfig` (step size filled in), the corpus scale, and the
    chunked epoch shape.  ``why`` records each planner decision.
    """
    spec: ExperimentSpec
    backend: str          # one of BACKENDS
    placement: str        # STREAMED | RESIDENT
    kernel: str           # EAGER | FUSED
    fmt: str              # DENSE | CSR (ARRAYS lowers to DENSE)
    cfg: SolverConfig     # resolved solver config (step size, flags)
    rows: int
    features: int
    num_batches: int      # m, batches per epoch
    chunk: int            # K, batches per device call (m when resident)
    corpus_bytes: int
    kmax: int = 0         # densest CSR row (sparse only)
    nnz: int = 0          # stored nonzeros (sparse only)
    shards: int = 1       # data-parallel width (1 = single-host backends)
    reduction: Optional[str] = None     # GATHER | PSUM (sharded only)
    why: Tuple[str, ...] = ()

    @property
    def density(self) -> float:
        return self.nnz / max(1, self.rows * self.features)

    @property
    def scheme_obj(self) -> schemes.Scheme:
        """The canonical Scheme object (spec strings resolved)."""
        return schemes.resolve(self.spec.scheme)

    @property
    def scheme_name(self) -> str:
        """Canonical scheme name — what describe()/to_json/the fingerprint
        record, identical for a legacy string spec and the object form."""
        return self.scheme_obj.name

    @property
    def step_rule(self) -> str:
        """The resolved step rule, e.g. ``constant`` or
        ``line_search[vectorized]`` — the ``ls_mode`` axis the benchmark
        records."""
        if self.cfg.step_mode == LINE_SEARCH:
            return f"{LINE_SEARCH}[{self.cfg.ls_mode}]"
        return self.cfg.step_mode

    def describe(self) -> str:
        lines = [
            f"backend   : {self.backend}",
            f"data      : {self.fmt} {self.rows}x{self.features} "
            f"({self.corpus_bytes / 1e6:.1f} MB"
            + (f", nnz={self.nnz}, kmax={self.kmax}" if self.fmt == CSR
               else "") + ")",
            f"method    : {self.cfg.solver}/{self.step_rule} under "
            f"{self.scheme_name}"
            + (f"{self.scheme_obj.params()}" if self.scheme_obj.params()
               else "")
            + f" sampling, step={self.cfg.step_size:.3g}",
            f"epoch     : m={self.num_batches} batches of "
            f"{self.spec.batch_size}, {self.chunk} per device call, "
            f"{self.spec.epochs} epochs",
        ]
        if self.shards > 1:
            lines.append(f"mesh      : {self.shards}-way data parallel, "
                         f"{self.reduction} reduction")
        lines += [f"  - {w}" for w in self.why]
        return "\n".join(lines)


@dataclasses.dataclass
class _Probe:
    """What the planner learned by looking at the data source."""
    fmt: str
    rows: int
    features: int
    nbytes: int
    kmax: int = 0
    nnz: int = 0


def _probe(data: DataSource) -> _Probe:
    if data.kind == ARRAYS:
        X, y = data.X, data.y
        return _Probe(DENSE, X.shape[0], X.shape[1],
                      int(X.nbytes + np.asarray(y).nbytes))
    if data.path is None:
        raise PlanError("corpus DataSource has no path")
    if data.kind == CSR:
        from ..data import sparse
        csr = sparse.open_csr_corpus(data.path)
        return _Probe(CSR, csr.rows, csr.features, csr.meta.nbytes,
                      kmax=csr.kmax, nnz=csr.nnz)
    from ..data import dataset
    _, meta = dataset.open_corpus(data.path)
    return _Probe(DENSE, meta.rows, meta.row_dim - 1, meta.nbytes)


def _fused_support(spec: ExperimentSpec, probe: _Probe) -> Tuple[bool, str]:
    """(supported, reason-if-not) for the fused Pallas gradient kernels."""
    if probe.fmt == CSR:
        return False, ("fused kernels are dense-only; CSR corpora run the "
                       "padded-ELL engines (sparse-csr, resident-sparse)")
    try:
        from ..kernels import fused_erm  # pallas availability
    except ImportError:
        return False, "pallas/fused kernels unavailable in this environment"
    # the kernel module's OWN support set, not this planner's loss enum
    if spec.loss not in fused_erm.LOSSES:
        return False, f"loss {spec.loss!r} has no fused kernel"
    return True, ""


def _resident_budget(spec: ExperimentSpec) -> int:
    if spec.resident_budget is not None:
        return spec.resident_budget
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return DEFAULT_RESIDENT_BUDGET
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise PlanError(
            f"{dev.device_kind} reports no memory_stats()['bytes_limit'], so "
            "placement='auto' cannot size the device; pass resident_budget= "
            "or force placement=")
    # leave headroom for solver state, staging and compiler scratch
    return int(limit * 0.6)


def _ell_resident_bytes(probe: _Probe) -> int:
    """Device bytes of a CSR corpus staged as padded ELL: int32 column ids
    and float32 values of ``rows x kmax``, kmax rounded up to the lanes,
    plus the float32 labels."""
    slots = probe.rows * (-(-probe.kmax // _LANES) * _LANES)
    return slots * 8 + probe.rows * 4


def plan(spec: ExperimentSpec, *, audit: bool = False) -> ExecutionPlan:
    """Lower a spec to an :class:`ExecutionPlan`, rejecting combinations
    that cannot run with a :class:`PlanError` that names the conflict.

    ``audit=True`` additionally runs the static access-contract audit
    (:func:`repro.analysis.audit.audit`) on the finished plan — every
    backend epoch function is lowered from abstract shapes, nothing
    executes — and raises :class:`repro.analysis.AuditError` (a
    :class:`PlanError`) if the lowered program drifts from the contract."""
    # ---- enum validation (fail with the full menu, not a KeyError later)
    if spec.solver not in SOLVERS:
        raise PlanError(f"unknown solver {spec.solver!r}; want one of {SOLVERS}")
    # ONE validator owns the sampling rules (Scheme.validate raises
    # ValueError); plan() re-raises as PlanError at its boundary, exactly
    # like the validate_ls arrangement below — so plan() users and direct
    # pipeline/bind users can never drift apart
    try:
        scheme_obj = schemes.resolve(spec.scheme)
        scheme_obj.validate(batch_size=spec.batch_size)
    except ValueError as e:
        raise PlanError(str(e)) from e
    if spec.step_mode not in (CONSTANT, LINE_SEARCH):
        raise PlanError(f"unknown step_mode {spec.step_mode!r}; want "
                        f"{(CONSTANT, LINE_SEARCH)}")
    if spec.ls_mode not in (AUTO,) + LS_MODES:
        raise PlanError(f"ls_mode must be auto/sequential/vectorized, got "
                        f"{spec.ls_mode!r}")
    # line-search hyperparameters that cannot terminate or cannot decrease
    # die HERE, not as an endless backtracking loop at run time — one
    # validator (step_rules.validate_ls) owns the rules so plan() and
    # direct SolverConfig users can never drift apart
    if spec.step_size is not None and not spec.step_size > 0:
        raise PlanError(f"step_size must be positive (got "
                        f"{spec.step_size!r}) — it is the constant step or "
                        f"the line search's initial trial")
    try:
        validate_ls(1.0 if spec.step_size is None else spec.step_size,
                    spec.ls_shrink, spec.ls_c, spec.ls_max_iter)
    except ValueError as e:
        raise PlanError(str(e)) from e
    if spec.loss not in LOSSES:
        raise PlanError(f"unknown loss {spec.loss!r}; want one of {LOSSES}")
    if spec.placement not in (AUTO, STREAMED, RESIDENT):
        raise PlanError(f"placement must be auto/streamed/resident, got "
                        f"{spec.placement!r}")
    if spec.kernel not in (AUTO, FUSED, EAGER):
        raise PlanError(f"kernel must be auto/fused/eager, got {spec.kernel!r}")
    if spec.reduction not in (AUTO, GATHER, PSUM):
        raise PlanError(f"reduction must be auto/gather/psum, got "
                        f"{spec.reduction!r}")
    if spec.mesh is None and spec.reduction != AUTO:
        raise PlanError(
            "reduction= picks how a device mesh combines per-device work; "
            "it needs mesh= (leave it 'auto' for single-host runs)")
    if spec.batch_size <= 0 or spec.epochs <= 0:
        raise PlanError("batch_size and epochs must be positive")
    if spec.checkpoint is not None:
        if not isinstance(spec.checkpoint, CheckpointPolicy):
            raise PlanError(
                f"checkpoint= wants a repro.checkpoint.CheckpointPolicy, "
                f"got {type(spec.checkpoint).__name__}")
        try:
            spec.checkpoint.validate()
        except ValueError as e:
            raise PlanError(str(e)) from e
    if spec.trace is not None:
        if not isinstance(spec.trace, TracePolicy):
            raise PlanError(
                f"trace= wants a repro.obs.TracePolicy, "
                f"got {type(spec.trace).__name__}")
        try:
            spec.trace.validate()
        except ValueError as e:
            raise PlanError(str(e)) from e

    # ---- adaptive schemes: host-feedback sampling constrains the lowering
    if scheme_obj.adaptive:
        if spec.step_mode == LINE_SEARCH:
            raise PlanError(
                f"scheme {scheme_obj.name!r} emits importance-weighted "
                "gradients, but line search probes the UNWEIGHTED (and, for "
                "stochastic batch size, zero-padded) batch objective — the "
                "VectorizedLS trial ladder's Armijo comparison would mix "
                "the two normalizations; use step_mode='constant'")
        if spec.placement == RESIDENT or spec.data.kind == ARRAYS:
            raise PlanError(
                f"scheme {scheme_obj.name!r} picks each batch on the host "
                "(per-step draws + feedback), which a resident in-graph "
                "epoch cannot replay; it needs a streamed corpus "
                "(placement='streamed' over DataSource.corpus)")
        if spec.kernel == FUSED:
            raise PlanError(
                f"scheme {scheme_obj.name!r} needs the streamed engine; "
                "fused kernels sample from a device-resident corpus")
        if spec.mesh is not None and data_parallel_width(spec.mesh) > 1:
            raise PlanError(
                f"scheme {scheme_obj.name!r} is single-host for now: the "
                "sharded staging path does not carry the per-batch "
                "slot/weight schedule (ROADMAP follow-on)")

    probe = _probe(spec.data)
    if spec.batch_size > probe.rows:
        raise PlanError(
            f"batch_size {spec.batch_size} exceeds the corpus "
            f"({probe.rows} rows) — the samplers pad the TRAILING batch by "
            f"wrap-around, they don't oversample the whole corpus")
    why: List[str] = []

    # ---- data parallelism: mesh width and reduction mode -----------------
    shards = data_parallel_width(spec.mesh)
    reduction = None
    if shards > 1:
        if probe.fmt == CSR:
            raise PlanError(
                "sharded placement splits dense (l, n) chunks on the batch "
                "axis; CSR corpora run single-device ELL engines, streamed "
                "or resident (sharded CSR staging is a ROADMAP follow-on)")
        if spec.kernel == FUSED:
            raise PlanError(
                "kernel='fused' rejected under a >1-device mesh: the fused "
                "kernels' DMA scheduling assumes a single-device resident "
                "corpus; sharded placements run the eager engines")
        if spec.batch_size % shards != 0:
            raise PlanError(
                f"batch_size {spec.batch_size} does not divide across the "
                f"{shards}-way mesh batch axis — staged chunks would "
                f"silently replicate instead of sharding; pick a batch size "
                f"divisible by {shards}")
        reduction = GATHER if spec.reduction == AUTO else spec.reduction
        if spec.reduction == AUTO:
            why.append(f"{shards}-way mesh → 'gather' reduction: chunks "
                       "stage sharded (per-device H2D /"
                       f"{shards}), then replicate at the jit boundary — "
                       "bit-identical to the single-host trajectory "
                       "(reduction='psum' also divides compute, at ulp-"
                       "level trajectory drift)")
        else:
            why.append(f"reduction {reduction!r} forced by spec on the "
                       f"{shards}-way mesh")
    elif spec.mesh is not None:
        if spec.mesh.devices.size > 1:
            # a multi-device mesh that resolves to width 1 means the batch
            # axis cannot map onto it — falling back silently would ignore
            # the user's parallelism request
            raise PlanError(
                f"mesh has {spec.mesh.devices.size} devices but its axes "
                f"{spec.mesh.axis_names} include none of the batch-axis "
                f"names ('pod', 'data') — name a data-parallel axis "
                f"'data' (e.g. jax.make_mesh((N,), ('data',)))")
        if spec.reduction != AUTO:
            raise PlanError(
                f"reduction={spec.reduction!r} forced on a 1-device mesh — "
                f"there is no per-device work to combine; sharded "
                f"placement needs >1 data-parallel devices")
        why.append("1-device mesh → single-host backends (sharded "
                   "placement needs >1 data-parallel devices)")

    # ---- placement: streamed vs resident --------------------------------
    if spec.data.kind == ARRAYS:
        if spec.placement == STREAMED:
            raise PlanError("in-memory arrays have no corpus to stream; use "
                            "a DataSource.corpus(...) for streamed placement")
        placement = RESIDENT
        why.append("arrays are device-resident by construction")
    elif probe.fmt == CSR:
        if spec.placement == RESIDENT:
            ell, budget = _ell_resident_bytes(probe), _resident_budget(spec)
            if ell > budget:
                raise PlanError(
                    f"resident placement stages the CSR corpus as padded "
                    f"ELL, {ell / 1e6:.1f} MB ({probe.rows} rows x "
                    f"{probe.kmax} slots, lanes of {_LANES}), over the "
                    f"{budget / 1e6:.0f} MB device budget; stream it "
                    f"(placement='streamed')")
            placement = RESIDENT
            why.append(f"CSR corpus, resident placement forced → staged "
                       f"once as padded ELL, {ell / 1e6:.1f} MB within the "
                       f"{budget / 1e6:.0f} MB device budget: the "
                       "resident-sparse engine")
        else:
            placement = STREAMED
            why.append("CSR corpus → streamed sparse engine (resident "
                       "placement must be forced)")
    elif scheme_obj.adaptive:
        placement = STREAMED
        why.append(f"{scheme_obj.name} sampling picks batches on the host "
                   "(per-step draws + feedback) → streamed placement; "
                   "pipeline read-ahead is disabled so the scheme state is "
                   "exact at every epoch boundary")
    elif spec.placement != AUTO:
        placement = spec.placement
        why.append(f"placement {placement!r} forced by spec")
    else:
        budget = _resident_budget(spec)
        # psum keeps the corpus sharded through the epoch scan, so each
        # device only holds its 1/shards slice; gather replicates at the
        # jit boundary and needs the full corpus per device
        nbytes_eff = probe.nbytes // (shards if reduction == PSUM else 1)
        per_dev = " per device" if reduction == PSUM else ""
        if nbytes_eff <= budget:
            placement = RESIDENT
            why.append(f"corpus {nbytes_eff / 1e6:.1f} MB{per_dev} fits the "
                       f"{budget / 1e6:.0f} MB device budget → resident")
        else:
            placement = STREAMED
            why.append(f"corpus {nbytes_eff / 1e6:.1f} MB{per_dev} exceeds "
                       f"the {budget / 1e6:.0f} MB device budget → streamed")

    # ---- kernel: fused vs eager ------------------------------------------
    ok, reason = _fused_support(spec, probe)
    if spec.kernel == FUSED:
        if not ok:
            raise PlanError(f"kernel='fused' rejected: {reason}")
        if placement != RESIDENT:
            raise PlanError(
                "kernel='fused' rejected: the fused gather+grad kernels "
                "sample from a device-resident corpus; the streamed engine "
                "consumes staged batches, which are materialized by "
                "construction (force placement='resident' or drop the "
                "kernel override)")
        kernel = FUSED
        why.append("fused kernels forced by spec")
    elif spec.kernel == EAGER or placement != RESIDENT:
        kernel = EAGER
    elif shards > 1:
        kernel = EAGER
        why.append("sharded placement runs the eager engines (fused kernel "
                   "scheduling under a device mesh is a follow-on)")
    elif not ok:
        kernel = EAGER
        why.append(f"fused kernels skipped: {reason}")
    elif jax.default_backend() != "tpu":
        # auto mode optimizes wall clock: off-TPU the kernels run only in
        # the CPU interpreter (a parity path, not a fast path)
        kernel = EAGER
        why.append("fused kernels available but interpret-only off TPU; "
                   "pass kernel='fused' to force")
    else:
        from ..kernels import fused_erm
        rows = fused_erm.kernel_rows(probe.rows, spec.batch_size)
        if rows != probe.rows:
            kernel = EAGER
            why.append(f"fused kernels skipped: they read row groups of 8, "
                       f"so the {probe.rows}-row corpus would be padded to "
                       f"{rows} rows by a copy on every call; pass "
                       "kernel='fused' to force")
        else:
            kernel = FUSED
            why.append("resident + supported loss → fused kernels by "
                       "default (line search runs on the fused margin "
                       "kernels)")

    # ---- chunk shape (streamed) and solver config ------------------------
    m = samplers.num_batches(probe.rows, spec.batch_size)
    if placement == RESIDENT:
        chunk = m      # whole epoch per device call, in-graph selection
        if spec.chunk is not None:
            # not an error (auto placement may legitimately pick resident),
            # but never silent: the override has no effect here
            why.append(f"spec.chunk={spec.chunk} ignored: resident runs the "
                       "whole epoch in-graph, there is no staged chunking")
    else:
        if spec.chunk is not None:
            chunk = max(1, min(spec.chunk, m))
            why.append(f"chunk K={chunk} forced by spec")
        else:
            if probe.fmt == CSR:
                per_batch = spec.batch_size * (probe.kmax * 8 + 4)
            else:
                per_batch = spec.batch_size * (probe.features + 1) * 4
            chunk = max(1, min(_CHUNK_BYTE_BUDGET // max(per_batch, 1), m))

    step_size = (spec.step_size if spec.step_size is not None
                 else _auto_step_size(spec, probe))
    ls_mode = VECTORIZED if spec.ls_mode == AUTO else spec.ls_mode
    if spec.step_mode == LINE_SEARCH:
        if spec.ls_mode == AUTO:
            why.append("line search lowers to the vectorized trial-ladder "
                       "sweep (ls_mode='sequential' keeps the backtracking "
                       "while_loop reference)")
        else:
            why.append(f"ls_mode {ls_mode!r} forced by spec")
    if spec.checkpoint is not None:
        pol = spec.checkpoint
        why.append(f"durable run: checkpoint every {pol.every} epoch(s) to "
                   f"{pol.directory} (keep {pol.keep}, "
                   f"{'async' if pol.async_save else 'blocking'} saves)")
    if spec.trace is not None:
        tp = spec.trace
        why.append(
            ("traced run: span timeline over a "
             f"{tp.buffer}-event ring buffer"
             + (f", Chrome trace to {tp.path}" if tp.path else ""))
            if tp.enabled else
            "trace policy present but disabled → near-zero-cost no-op "
            "spans (the A/B overhead knob)")
    cfg = SolverConfig(solver=spec.solver, step_mode=spec.step_mode,
                       step_size=step_size, ls_shrink=spec.ls_shrink,
                       ls_c=spec.ls_c, ls_max_iter=spec.ls_max_iter,
                       ls_mode=ls_mode, use_fused=(kernel == FUSED),
                       sparse=(probe.fmt == CSR))

    if probe.fmt == CSR:
        backend = RESIDENT_SPARSE if placement == RESIDENT else SPARSE_CSR
    elif shards > 1:
        backend = (SHARDED_RESIDENT if placement == RESIDENT
                   else SHARDED_STREAMED)
    elif placement == RESIDENT:
        backend = RESIDENT_FUSED if kernel == FUSED else RESIDENT_EAGER
    else:
        backend = STREAMED_EAGER
    plan_ = ExecutionPlan(spec=spec, backend=backend, placement=placement,
                          kernel=kernel, fmt=probe.fmt, cfg=cfg,
                          rows=probe.rows, features=probe.features,
                          num_batches=m, chunk=chunk,
                          corpus_bytes=probe.nbytes, kmax=probe.kmax,
                          nnz=probe.nnz, shards=shards, reduction=reduction,
                          why=tuple(why))
    if audit:
        # late import: analysis lowers plans, so it imports this module
        from ..analysis.audit import check as _audit_check
        _audit_check(plan_)
    return plan_


def _auto_step_size(spec: ExperimentSpec, probe: _Probe) -> float:
    """Paper §4.1 defaults: constant step = 1/L, line search starts at 1."""
    if spec.step_mode == LINE_SEARCH:
        return 1.0
    problem = spec.problem
    if probe.fmt == CSR:
        from ..data import sparse
        return 1.0 / sparse.csr_lipschitz(problem, sparse.open_csr_corpus(
            spec.data.path))
    if spec.data.kind == ARRAYS:
        sample = jnp.asarray(spec.data.X[:_STEP_SAMPLE_ROWS])
    else:
        from ..data import dataset
        mm, meta = dataset.open_corpus(spec.data.path)
        sample = jnp.asarray(mm[:_STEP_SAMPLE_ROWS, :meta.row_dim - 1])
    return 1.0 / float(problem.lipschitz(sample))


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunResult:
    """Uniform outcome of :func:`execute` across every backend.

    ``history`` is the CUMULATIVE per-epoch objective trace: a resumed call
    prepends the trace the ``resume`` result carried, so after any chain of
    ``execute(plan, resume=prev)`` segments (in-memory or reconstructed
    from disk by :func:`resume_from`) it reads exactly like one
    uninterrupted run's.  Empty when ``spec.record_objective`` is off —
    ``objective`` is always the final full-corpus value.
    ``solver_state``/``sampler_state`` resume the run: pass the result back
    as ``execute(plan, resume=result)`` and the batch schedule continues
    exactly where an uninterrupted run would be.  (``solver_state`` is
    ``None`` on results rebuilt by :meth:`from_json` — JSON carries the
    summary surface; on-disk checkpoints carry resumable state.)
    """
    plan: ExecutionPlan
    objective: float
    history: np.ndarray
    w: np.ndarray
    solver_state: SolverState
    sampler_state: Dict
    epochs_run: int            # epochs executed by THIS call
    epochs_done: int           # cumulative, including resumed-from epochs
    stats: "AccessStats"       # noqa: F821 — repro.data.pipeline.AccessStats
    train_s: float
    compute_s: float
    # span timeline of THIS execute() call (same per-call basis as stats),
    # present when the spec carried an enabled TracePolicy; results rebuilt
    # by from_json carry the metrics snapshot with no span events
    timeline: Optional[Timeline] = None

    def breakdown(self) -> Dict[str, float]:
        """Per-epoch wall-clock decomposition in the BENCH_erm schema."""
        st, e = self.stats, max(self.epochs_run, 1)
        m, K = self.plan.num_batches, self.plan.chunk
        out = {"epoch_s": self.train_s / e,
               "compute_s_per_epoch": self.compute_s / e,
               "access_mb_per_s": st.read_mb_per_s,
               "objective": self.objective}
        if self.plan.placement == RESIDENT:
            out.update(
                access_s_per_epoch=st.access_s / e,      # one-time, amortized
                h2d_s_per_epoch=st.h2d_s / e,
                h2d_saved_s_per_epoch=st.h2d_saved_s / e,
                access_mb_per_epoch=st.read_mb / e)
        else:
            out.update(
                access_s_per_epoch=st.s_per_batch * m,   # producer thread
                h2d_s_per_epoch=st.h2d_s / max(st.staged, 1) * (-(-m // K)),
                access_mb_per_epoch=st.read_mb / max(st.batches, 1) * m)
        if st.shards > 1:
            # per-device access accounting: staged bytes split `shards` ways
            # on the batch axis; gather_s is the D2D replication slice of
            # h2d_s ('gather' reduction only)
            out.update(shards=st.shards,
                       h2d_mb_per_device=st.h2d_bytes_per_device / 1e6,
                       gather_s_per_epoch=st.gather_s / e)
        return out

    def save_trace(self, path) -> Path:
        """Write the span timeline as Chrome/Perfetto trace-event JSON —
        open it in ``chrome://tracing`` or https://ui.perfetto.dev."""
        if self.timeline is None or not self.timeline.events:
            raise ValueError(
                "this result carries no span timeline — run with "
                "ExperimentSpec.trace=TracePolicy() (results rebuilt from "
                "JSON carry only the metrics snapshot)")
        return self.timeline.save(path)

    def verify_timeline(self, tol: float = 0.05) -> Dict[str, Dict]:
        """Assert the span timeline reconciles with the stats accounting.

        Two layers of invariant, both returned in the report (and raised
        as one ``ValueError`` naming every violation):

        * **exact basis** — each accounting lane's toplevel span sum IS the
          sum of the measurements :class:`AccessStats` booked (they share
          the ``timespan`` measurement by construction), so access / h2d /
          gather lanes match ``stats`` and the compute lane matches
          ``compute_s`` to float noise;
        * **breakdown** — the per-epoch estimates of :meth:`breakdown`
          times ``epochs_run`` match the lane sums within ``tol``.  On a
          streamed run the trace additionally records the prefetch
          producer's overrun reads (a few batches past the last one the
          epoch loop consumed) which :meth:`breakdown`'s steady-state
          per-batch estimator deliberately excludes, so the access
          comparison is made in per-batch units — the overrun is a fixed
          few batches, which would swamp ``tol`` on an 8-batch smoke run
          while being invisible on a real one.
        """
        if self.timeline is None or not self.timeline.events:
            raise ValueError(
                "no span timeline to verify — run with "
                "ExperimentSpec.trace=TracePolicy()")
        if self.timeline.dropped:
            raise ValueError(
                f"{self.timeline.dropped} spans were evicted from the ring "
                f"buffer; lane sums would undercount — raise "
                f"TracePolicy.buffer")
        lanes = self.timeline.lane_totals()
        st, e = self.stats, max(self.epochs_run, 1)
        bd = self.breakdown()
        report: Dict[str, Dict] = {}
        bad: List[str] = []

        def check(name: str, span_s: float, ref_s: float, rel: float):
            slack = max(rel * max(abs(ref_s), abs(span_s)), 1e-4)
            ok = abs(span_s - ref_s) <= slack
            report[name] = {"span_s": span_s, "ref_s": ref_s, "ok": ok}
            if not ok:
                bad.append(f"{name}: span sum {span_s:.6f}s vs reference "
                           f"{ref_s:.6f}s (tolerance {slack:.6f}s)")

        check("access_vs_stats", lanes.get(ACCESS, 0.0), st.access_s, 1e-6)
        check("h2d_vs_stats", lanes.get(H2D, 0.0), st.h2d_s, 1e-6)
        check("gather_vs_stats", lanes.get(GATHER_LANE, 0.0), st.gather_s,
              1e-6)
        check("compute_vs_stats", lanes.get(COMPUTE, 0.0), self.compute_s,
              1e-6)
        access_span = lanes.get(ACCESS, 0.0)
        if self.plan.placement != RESIDENT and st.batches > 0:
            # per-batch units: scale the span sum down to the m*e batches
            # breakdown() accounts for (the remainder is producer overrun)
            consumed = self.plan.num_batches * e
            access_span *= min(1.0, consumed / st.batches)
        check("access_vs_breakdown", access_span,
              bd["access_s_per_epoch"] * e, tol)
        check("h2d_vs_breakdown", lanes.get(H2D, 0.0),
              bd["h2d_s_per_epoch"] * e, tol)
        check("compute_vs_breakdown", lanes.get(COMPUTE, 0.0),
              bd["compute_s_per_epoch"] * e, tol)
        if bad:
            raise ValueError(
                "span timeline does not reconcile with the access/compute "
                "accounting:\n  " + "\n  ".join(bad))
        return report

    def to_json(self) -> Dict:
        """JSON-safe summary (the CI artifact schema) — resumable state is
        the sampler side only; the solver pytree stays in memory (or on
        disk, when the spec carries a :class:`CheckpointPolicy`).  Schema 2
        adds ``w``/``train_s``/``compute_s`` so :meth:`from_json` can
        rebuild the full summary surface, per-device stats included;
        schema 3 adds the ``metrics`` block (counter/histogram
        snapshot of a traced run — ``{}`` untraced; span events stay in
        the separate Chrome-trace artifact, see :meth:`save_trace`)."""
        p = self.plan
        return {
            "schema": 3,
            "backend": p.backend,
            "plan": {"placement": p.placement, "kernel": p.kernel,
                     "format": p.fmt, "solver": p.cfg.solver,
                     "step_mode": p.cfg.step_mode,
                     "ls_mode": (p.cfg.ls_mode
                                 if p.cfg.step_mode == LINE_SEARCH else None),
                     "step_size": p.cfg.step_size, "scheme": p.scheme_name,
                     "scheme_params": p.scheme_obj.params(),
                     "batch_size": p.spec.batch_size, "rows": p.rows,
                     "features": p.features, "num_batches": p.num_batches,
                     "chunk": p.chunk, "corpus_bytes": p.corpus_bytes,
                     "devices": p.shards, "reduction": p.reduction,
                     "why": list(p.why)},
            "epochs_run": self.epochs_run,
            "epochs_done": self.epochs_done,
            "objective": self.objective,
            "history": [float(h) for h in self.history],
            "w": [float(v) for v in self.w],
            "w_norm": float(np.linalg.norm(self.w)),
            "sampler_state": self.sampler_state,
            "train_s": self.train_s,
            "compute_s": self.compute_s,
            "breakdown": self.breakdown(),
            "stats": {**dataclasses.asdict(self.stats),
                      "h2d_bytes_per_device":
                          self.stats.h2d_bytes_per_device},
            "metrics": (self.timeline.metrics
                        if self.timeline is not None else {}),
        }

    def save_json(self, path) -> Path:
        """Write :meth:`to_json` atomically (tmp + ``os.replace``): a crash
        mid-write can never leave a truncated artifact that poisons a later
        reader."""
        return atomic_write_text(path,
                                 json.dumps(self.to_json(), indent=2) + "\n")

    @staticmethod
    def from_json(source, plan_: "ExecutionPlan") -> "RunResult":
        """Rebuild the JSON surface of a saved result against ``plan_``.

        The returned result reproduces :meth:`to_json` bit-for-bit —
        objective trace, weights, wall-clock, and the per-device access
        stats of sharded runs included — but carries ``solver_state=None``:
        the solver pytree is not in the JSON, so it supports every summary
        consumer while ``execute(resume=)`` rejects it (reconstruct
        resumable state from a checkpoint via :func:`resume_from`).
        """
        d = source
        if not isinstance(d, dict):
            d = json.loads(Path(source).read_text())
        want = {"backend": plan_.backend, "solver": plan_.cfg.solver,
                "scheme": plan_.scheme_name, "rows": plan_.rows,
                "devices": plan_.shards}
        got = {"backend": d["backend"], "solver": d["plan"]["solver"],
               "scheme": d["plan"]["scheme"], "rows": d["plan"]["rows"],
               "devices": d["plan"]["devices"]}
        if want != got:
            bad = [f"{k}: json {got[k]!r} != plan {want[k]!r}"
                   for k in want if got[k] != want[k]]
            raise ValueError("saved RunResult JSON does not describe this "
                             "plan; differing fields:\n  " + "\n  ".join(bad))
        from ..data import pipeline as pipemod
        fields = {f.name for f in dataclasses.fields(pipemod.AccessStats)}
        stats = pipemod.AccessStats(**{k: v for k, v in d["stats"].items()
                                       if k in fields})
        # schema 3 carries the metrics snapshot; span events live in the
        # separate Chrome-trace artifact, so the rebuilt timeline is
        # metrics-only (to_json round-trips bit-for-bit either way)
        metrics = d.get("metrics") or {}
        timeline = Timeline(events=[], metrics=metrics) if metrics else None
        return RunResult(
            plan=plan_, objective=d["objective"],
            history=np.asarray(d["history"]),
            w=np.asarray(d["w"], np.float32), solver_state=None,
            sampler_state=d["sampler_state"],
            epochs_run=d["epochs_run"],
            epochs_done=d["epochs_done"], stats=stats,
            train_s=d["train_s"], compute_s=d["compute_s"],
            timeline=timeline)


# ---------------------------------------------------------------------------
# plan identity: what a resume / restore must match
# ---------------------------------------------------------------------------

# STRICT fields pin the trajectory arithmetic and the batch schedule — a
# checkpoint restored under a different value of any of these would not
# continue the same run.  ELASTIC fields may change across a restart: the
# mesh width / reduction family (within the bit-identical gather ∪
# single-host family), the chunk shape, and the epoch budget reshape HOW
# the same trajectory executes, not WHAT it computes.
_FP_STRICT = ("solver", "scheme", "scheme_params", "loss", "reg", "seed",
              "batch_size",
              "step_mode", "step_size", "ls_mode", "ls_shrink", "ls_c",
              "ls_max_iter", "record_objective", "data", "fmt", "rows",
              "features", "num_batches", "placement", "kernel")
_FP_ELASTIC = ("backend", "chunk", "shards", "reduction", "epochs")


def _plan_fingerprint(p: ExecutionPlan) -> Dict:
    """JSON-safe identity of a plan, stored in every checkpoint's meta and
    validated by :func:`resume_from` before any array is loaded."""
    s = p.spec
    return {
        "solver": p.cfg.solver, "scheme": p.scheme_name,
        "scheme_params": p.scheme_obj.params(), "loss": s.loss,
        "reg": s.reg, "seed": s.seed, "batch_size": s.batch_size,
        "step_mode": p.cfg.step_mode, "step_size": p.cfg.step_size,
        "ls_mode": p.cfg.ls_mode, "ls_shrink": p.cfg.ls_shrink,
        "ls_c": p.cfg.ls_c, "ls_max_iter": p.cfg.ls_max_iter,
        "record_objective": s.record_objective,
        "data": str(s.data.path) if s.data.path is not None else None,
        "fmt": p.fmt, "rows": p.rows, "features": p.features,
        "num_batches": p.num_batches, "placement": p.placement,
        "kernel": p.kernel,
        "backend": p.backend, "chunk": p.chunk, "shards": p.shards,
        "reduction": p.reduction, "epochs": s.epochs,
    }


def _validate_fingerprint(saved: Dict, plan_: ExecutionPlan) -> None:
    """Field-by-field check that a checkpoint belongs to ``plan_``.

    Strict fields must match exactly.  'psum' reduction additionally pins
    ``shards``/``reduction``/``backend``: its per-device partial-gradient
    combine is deterministic PER MESH, so a psum trajectory cannot continue
    on a different width (the gather ∪ single-host family is bit-identical
    across widths and restores elastically).
    """
    cur = _plan_fingerprint(plan_)
    bad = [f"{k}: checkpoint {saved.get(k)!r} != plan {cur[k]!r}"
           for k in _FP_STRICT if saved.get(k) != cur[k]
           # checkpoints written before the Scheme protocol carry no
           # scheme_params block; the scheme NAME (always present) still
           # pins the schedule for those uniform-scheme runs
           and not (k == "scheme_params" and k not in saved)]
    if PSUM in (saved.get("reduction"), cur["reduction"]):
        bad += [f"{k}: checkpoint {saved.get(k)!r} != plan {cur[k]!r} "
                f"(reduction='psum' pins the mesh)"
                for k in ("shards", "reduction", "backend")
                if saved.get(k) != cur[k]]
    if bad:
        raise ValueError(
            "checkpoint does not belong to this plan — a restored run must "
            "continue the SAME plan (mesh width, gather/single-host "
            "reduction, chunking and epoch budget may change; everything "
            "else pins the trajectory); differing fields:\n  "
            + "\n  ".join(bad))


def _fmt_mesh(m: Optional[Mesh]) -> Optional[str]:
    if m is None:
        return None
    return "Mesh(" + ", ".join(f"{n}={s}" for n, s in
                               zip(m.axis_names, m.devices.shape)) + ")"


def _plan_diff(a: ExecutionPlan, b: ExecutionPlan) -> List[str]:
    """Human-readable field-by-field differences between two plans, for
    the ``execute(resume=)`` rejection message — naming WHICH fields
    diverged beats re-deriving them from two plan reprs."""
    diffs = []
    for f in dataclasses.fields(ExperimentSpec):
        va, vb = getattr(a.spec, f.name), getattr(b.spec, f.name)
        if f.name == "scheme":
            # a legacy string and the Scheme object it resolves to are the
            # same scheme — compare canonically
            va, vb = schemes.resolve(va), schemes.resolve(vb)
        if va != vb:
            if f.name == "mesh":
                va, vb = _fmt_mesh(va), _fmt_mesh(vb)
            diffs.append(f"spec.{f.name}: resume {va!r} != plan {vb!r}")
    for name in ("backend", "placement", "kernel", "fmt", "rows",
                 "features", "num_batches", "chunk", "shards", "reduction"):
        va, vb = getattr(a, name), getattr(b, name)
        if va != vb:
            diffs.append(f"plan.{name}: resume {va!r} != plan {vb!r}")
    for name in SolverConfig._fields:
        va, vb = getattr(a.cfg, name), getattr(b.cfg, name)
        if va != vb:
            diffs.append(f"cfg.{name}: resume {va!r} != plan {vb!r}")
    return diffs


class _RunCheckpointer:
    """Bridges an epoch loop to the :class:`Checkpointer`.

    Owns the cadence (every ``policy.every`` CUMULATIVE epochs, plus always
    the final epoch of the call, so a completed segment is resumable
    regardless of alignment) and packages the full resumable surface into
    each snapshot's meta: sampler state, cumulative objective trace,
    :class:`AccessStats` and the plan fingerprint.  The solver pytree is
    the checkpoint's array payload.  ``after_epoch`` runs OUTSIDE the
    timers: the host snapshot is synchronous (it must complete before the
    next epoch donates the state buffers), the disk write overlaps the
    next epoch when the policy is async.
    """

    def __init__(self, plan_: ExecutionPlan, done0: int, epochs: int,
                 tracer=NULL_TRACER):
        self.pol = plan_.spec.checkpoint
        self.ck = (Checkpointer(self.pol.directory, keep=self.pol.keep,
                                async_save=self.pol.async_save,
                                tracer=tracer)
                   if self.pol is not None else None)
        self.plan = plan_
        self.done0 = done0
        self.epochs = epochs

    def after_epoch(self, e: int, state: SolverState, sampler_state: Dict,
                    history: List[float], stats) -> None:
        if self.ck is None:
            return
        done = self.done0 + e + 1
        if done % self.pol.every and e + 1 < self.epochs:
            return
        meta = {
            "schema": 1,
            "epochs_done": done,
            "sampler_state": sampler_state,
            "history": [float(h) for h in history],
            "objective": float(history[-1]) if history else None,
            "plan": _plan_fingerprint(self.plan),
            "policy": {"every": self.pol.every, "keep": self.pol.keep,
                       "async_save": self.pol.async_save},
            "stats": dataclasses.asdict(stats),
        }
        self.ck.save(done, state, meta)

    def finish(self) -> None:
        # a crashed async write surfaces HERE, not silently — the run must
        # not report durable state it failed to persist
        if self.ck is not None:
            self.ck.wait()


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def execute(plan_: ExecutionPlan, *, resume: Optional[RunResult] = None,
            epochs: Optional[int] = None) -> RunResult:
    """Run a plan for ``epochs`` epochs (default: the spec's budget).

    ``resume`` continues from a previous result OF THE SAME PLAN: the solver
    state is copied (the stored result stays usable) and the sampler resumes
    at the exact step an uninterrupted run would be at.  A solo run is the
    one-cell case of the placement's driver, which
    :func:`~repro.core.supercell.execute_supercell` runs over S cells.
    """
    # first, so the timeline's origin is the call's start: every second of
    # the call lies on the tracer's clock
    tracer = (plan_.spec.trace.make_tracer()
              if plan_.spec.trace is not None else NULL_TRACER)
    epochs = plan_.spec.epochs if epochs is None else epochs
    if resume is not None:
        _validate_resume(plan_, resume)
    with _compile_events(tracer):
        [result] = _drive([plan_], [resume], epochs, tracer, [tracer])
    return _attach_timeline(plan_, tracer, result)


def _validate_resume(plan_: ExecutionPlan, resume: RunResult) -> None:
    """Raise unless ``resume`` can continue ``plan_``."""
    if resume.solver_state is None:
        raise ValueError(
            "resume result carries no solver state (RunResult.from_json "
            "rebuilds the summary surface only) — reconstruct resumable "
            "state from an on-disk checkpoint via "
            "repro.api.resume_from(directory)")
    prev, cur = resume.plan.spec.data, plan_.spec.data
    # DataSource equality deliberately excludes array payloads (specs
    # stay hashable), so in-memory sources additionally require the
    # SAME arrays — resuming SAG/SAGA gradient memory against other
    # data would silently corrupt the run
    same_arrays = (prev.kind != ARRAYS
                   or (prev.X is cur.X and prev.y is cur.y))
    # identity is the RESOLVED trajectory (fingerprint + psum rule),
    # not raw spec equality: a plan rebuilt from a checkpoint's
    # fingerprint forces fields the original spec left 'auto', and the
    # elastic fields (mesh width, chunking, epoch budget) may change
    # across a restart
    try:
        _validate_fingerprint(_plan_fingerprint(resume.plan), plan_)
        same_run = True
    except ValueError:
        same_run = False
    if not same_run or not same_arrays:
        diffs = _plan_diff(resume.plan, plan_)
        if not same_arrays:
            diffs.append("spec.data: in-memory sources must be the "
                         "same arrays (X/y object identity)")
        raise ValueError(
            "resume result came from a different plan than the one "
            "being executed — a resumed run must continue the SAME "
            "plan (and, for in-memory sources, the same arrays) or the "
            "batch schedule silently diverges from an uninterrupted "
            "run; differing fields:\n  "
            + "\n  ".join(diffs
                          or ["(plans compare unequal with no "
                              "field-level difference)"]))


def _attach_timeline(plan_: ExecutionPlan, tracer: Tracer,
                     result: RunResult) -> RunResult:
    if tracer.enabled:
        # the timeline is PER-CALL, like stats: each segment of a resumed
        # run carries (and writes) its own trace
        result.timeline = tracer.timeline()
        if plan_.spec.trace.path is not None:
            result.timeline.save(plan_.spec.trace.path)
    return result


@contextlib.contextmanager
def _compile_events(tracer: Tracer):
    """While an enabled tracer is live, JAX's trace / compile / cache-load
    events land on it (``driver:compile``, ``jit.*`` counters)."""
    if not tracer.enabled:
        yield
        return
    jax.monitoring.register_event_duration_secs_listener(tracer.jax_event)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(tracer.jax_event)


def run_experiment(spec: ExperimentSpec) -> RunResult:
    """``execute(plan(spec))`` — the one-call path."""
    return execute(plan(spec))


def _drive(plans: Sequence[ExecutionPlan],
           resumes: Sequence[Optional[RunResult]], epochs: int,
           tracer: Tracer, tracers: Sequence[Tracer]) -> List[RunResult]:
    """Run S >= 1 cells that share one data plan (``plans[0]`` fixes it):
    the data side runs once, on ``tracer``; each cell's solver runs on the
    shared device data, its compute spans and counters on its own entry of
    ``tracers``.  A solo run passes its own tracer as both."""
    drive = (_drive_resident if plans[0].placement == RESIDENT
             else _drive_streamed)
    return drive(list(plans), list(resumes), epochs, tracer, list(tracers))


def _cell_stats(shared, cells: int):
    """The shared stream's :class:`AccessStats`, attributed to one of
    ``cells`` cells: time and bytes divide by the cell count (one read
    served every cell), batch/stage counts stay — ``s_per_batch`` then
    reads as the AMORTIZED per-batch access time, which is the quantity
    the paper's cost model multiplies by ``m``."""
    from ..data import pipeline as pipemod
    return pipemod.AccessStats(
        batches=shared.batches,
        access_s=shared.access_s / cells,
        bytes_read=shared.bytes_read // cells,
        staged=shared.staged,
        h2d_s=shared.h2d_s / cells,
        bytes_staged=shared.bytes_staged // cells,
        h2d_saved_s=shared.h2d_saved_s / cells,
        shards=shared.shards,
        gather_s=shared.gather_s / cells)


def _resume_state(plan_: ExecutionPlan, resume: Optional[RunResult],
                  ) -> Tuple[SolverState, int]:
    """(initial solver state, epochs already done).  The resumed state is
    COPIED: the chunked engines donate their state argument, and consuming
    the caller's stored result would break resuming twice."""
    if resume is None:
        w0 = jnp.zeros(plan_.features, jnp.float32)
        return init_state(plan_.cfg.solver, w0, plan_.num_batches), 0
    state = jax.tree_util.tree_map(jnp.array, resume.solver_state)
    return state, resume.epochs_done


# ---- resident backends -----------------------------------------------------

@partial(jax.jit, static_argnames=("problem",))
def _objective_jit(problem: ERMProblem, w: jax.Array, X: jax.Array,
                   y: jax.Array) -> jax.Array:
    # module-level so the compile cache survives across execute() calls —
    # a fresh jit(lambda ...) per call would retrace every time
    return problem.objective(w, X, y)


@partial(jax.jit, static_argnames=("problem", "rows"))
def _masked_objective_jit(problem: ERMProblem, rows: int, w: jax.Array,
                          X: jax.Array, y: jax.Array) -> jax.Array:
    # sharded 'psum' placement: the corpus carries zero-row padding so it
    # shards evenly — mask it out of the objective
    return problem.masked_objective(w, X, y, rows)


@partial(jax.jit, static_argnames=("problem", "n"))
def _block_loss_sum(problem: ERMProblem, n: int, w: jax.Array,
                    block: jax.Array, total: jax.Array) -> jax.Array:
    """``total`` plus the summed data loss of one block of dense corpus
    rows, put on the device flat as the file lays them out: ``n`` features,
    then the label.  A flat put carries no lane padding of the ``n + 1``
    wide rows; the split into X and y happens here, on the device."""
    rows = block.reshape(-1, n + 1)
    z = jnp.dot(rows[:, :n], w, precision=jax.lax.Precision.HIGHEST)
    return total + problem.sum_margin_loss(z, rows[:, n])


@partial(jax.jit, static_argnames=("problem",))
def _ell_objective_jit(problem: ERMProblem, w: jax.Array, X, y: jax.Array
                       ) -> jax.Array:
    # the full objective over a resident ELL corpus X = (cols, vals), in
    # row blocks on the device: one host read per pass
    return problem.ell_objective(w, *X, y)


def _warm_dense_objective(problem: ERMProblem, n: int, rows: int,
                          block_rows: int, w) -> None:
    """Compile :func:`_block_loss_sum` for every block length a pass over
    ``rows`` rows puts, against the ``w`` the pass will take."""
    w = jnp.asarray(w)
    for r in {min(block_rows, rows), rows % block_rows} - {0}:
        jax.block_until_ready(_block_loss_sum(
            problem, n, w, jnp.zeros(r * (n + 1), jnp.float32),
            jnp.zeros((), jnp.float32)))


def _dense_objectives(mm: np.ndarray, block_rows: int,
                      pairs: Sequence[Tuple[ERMProblem, object]],
                      tracer: Tracer = NULL_TRACER) -> List[float]:
    """The full objective of each ``(problem, w)`` pair over a dense corpus
    (``mm``: rows x (features + 1), the label last), in one streamed pass.

    Each contiguous block of ``block_rows`` rows is put on the device once,
    as it lies in the file, and scored there for every pair.  The partial
    sums stay on the device and are read once, at the end of the pass
    (counter ``objective.host_syncs``).  Before block i + 1 is put, the
    sums through block i - 1 are waited for: at most two blocks are on the
    device, and the host reads the next block while the device takes the
    last one.  A pair's value does not depend on the other pairs."""
    rows, n = mm.shape[0], mm.shape[1] - 1
    ws = [jnp.asarray(w) for _, w in pairs]
    totals = [jnp.zeros((), jnp.float32)] * len(pairs)
    for lo in range(0, rows, block_rows):
        block = jnp.asarray(np.asarray(mm[lo:lo + block_rows]).reshape(-1))
        sums = [_block_loss_sum(p, n, w, block, t)
                for (p, _), w, t in zip(pairs, ws, totals)]
        jax.block_until_ready(totals)
        totals = sums
    read = jax.device_get([(t, jnp.dot(w, w)) for t, w in zip(totals, ws)])
    if tracer.enabled:
        tracer.metrics.counter("objective.host_syncs").inc()
    return [float(s) / rows + 0.5 * p.reg * float(ww)
            for (p, _), (s, ww) in zip(pairs, read)]


@partial(jax.jit, static_argnames=("rows",))
def _trim_rows(a: jax.Array, rows: int) -> jax.Array:
    return a[:rows]


def _pad_rows(a: np.ndarray, to_rows: int) -> np.ndarray:
    if a.shape[0] == to_rows:
        return a
    pad = np.zeros((to_rows - a.shape[0],) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad])


def _stage_resident_sharded(plan_: ExecutionPlan, Xh: np.ndarray,
                            yh: np.ndarray, stats,
                            tracer=NULL_TRACER) -> Tuple[jax.Array,
                                                         jax.Array, float]:
    """Stage a host corpus across the mesh: zero-pad the rows so they shard
    evenly, place each device's slice over the host link (the same
    ``make_staging_put`` the streamed stager uses), and — in 'gather' mode —
    trim the padding after the put's reshard-to-replicated, so the epoch
    engine sees exactly the arrays the single-host backend would.  Returns
    ``(X, y, staging_seconds)``."""
    mesh, shards = plan_.spec.mesh, plan_.shards
    rows = Xh.shape[0]
    # pre-pad byte count: bytes_staged stays comparable with single-host
    # rows (the README's contract); the pad rows are a placement artifact
    nbytes = Xh.nbytes + yh.nbytes
    lpad = shards * (-(-rows // shards))
    Xh, yh = _pad_rows(Xh, lpad), _pad_rows(yh, lpad)
    stats.shards = max(stats.shards, shards)
    put = make_staging_put(mesh, (("batch", None), ("batch",)),
                           gather=plan_.reduction == GATHER, stats=stats,
                           tracer=tracer)
    with tracer.timespan("stage_resident", H2D, bytes=nbytes,
                         shards=shards) as sp:
        X, y = put((Xh, yh))
        if plan_.reduction == GATHER and lpad != rows:
            X, y = jax.block_until_ready((_trim_rows(X, rows),
                                          _trim_rows(y, rows)))
    h2d_dt = sp.dur
    stats.record_h2d(h2d_dt, nbytes)
    return X, y, h2d_dt


def _drive_resident(plans: List[ExecutionPlan],
                    resumes: List[Optional[RunResult]], epochs: int,
                    tracer: Tracer, tracers: List[Tracer]) -> List[RunResult]:
    """The resident engine: read the corpus once, stage it whole on the
    device (sharded across the mesh when the plan has one; a CSR corpus as
    padded-ELL ``(cols, vals)``), then run each epoch in one device call
    per cell.  The per-epoch objective and the checkpoints run outside the
    timers."""
    from ..data import pipeline as pipemod

    ref = plans[0]
    spec = ref.spec
    S, m = len(plans), ref.num_batches
    sharded = ref.shards > 1
    stats = pipemod.AccessStats()
    h2d_dt = 0.0

    if spec.data.kind == ARRAYS:
        if sharded:
            with tracer.span("layout", DRIVER) as sp:
                Xh = np.ascontiguousarray(np.asarray(spec.data.X, np.float32))
                yh = np.ascontiguousarray(np.asarray(spec.data.y, np.float32))
                sp.set(bytes=Xh.nbytes + yh.nbytes)
            X, y, h2d_dt = _stage_resident_sharded(ref, Xh, yh, stats,
                                                   tracer)
        else:
            X = jnp.asarray(spec.data.X, jnp.float32)
            y = jnp.asarray(spec.data.y, jnp.float32)
    else:
        if ref.fmt == CSR:
            from ..data.sparse import SparsePipeline as pipe_cls
        else:
            pipe_cls = pipemod.DataPipeline
        pipe = pipe_cls(pipemod.PipelineConfig(
            corpus=spec.data.path, batch_size=spec.batch_size,
            sampling=spec.scheme, seed=spec.seed, prefetch=0, resident=True),
            tracer=tracer)
        stats = pipe.stats
        # the corpus straight off the file, in one pass that lays it out:
        # contiguous X and y, or a CSR corpus padded to ELL (cols, vals, y).
        # Nothing is copied inside the H2D timer
        host = pipe.read_all()
        nbytes = sum(a.nbytes for a in host)
        if sharded:
            X, y, h2d_dt = _stage_resident_sharded(ref, *host, stats, tracer)
        else:
            fmt = {"fmt": "ell"} if ref.fmt == CSR else {}
            with tracer.timespan("stage_resident", H2D, bytes=nbytes,
                                 **fmt) as sp:
                # lint: allow[REPRO002] the accounted staging site:
                # the span IS the measurement record_h2d books below
                *X, y = jax.block_until_ready(
                    tuple(jax.device_put(a) for a in host))
            h2d_dt = sp.dur
            stats.record_h2d(h2d_dt, nbytes)
            X = tuple(X) if ref.fmt == CSR else X[0]
        # the host copies are dead once staged: free their pages here, on
        # the driver's clock, and not unseen at return
        with tracer.span("release", DRIVER, bytes=nbytes):
            del host

    # 'psum' keeps the padded corpus sharded through the scan, so the epoch
    # engine needs the true row count (schedule, clamping, masked snapshot
    # gradients); 'gather' and single-host see an unpadded corpus and run
    # the original program — the bit-parity surface
    psum = sharded and ref.reduction == PSUM
    fns = [make_resident_epoch_fn(p.spec.problem, p.cfg, ref.scheme_name,
                                  spec.batch_size,
                                  rows=ref.rows if psum else None)
           for p in plans]

    def obj(i, w):
        problem = plans[i].spec.problem
        if psum:
            return _masked_objective_jit(problem, ref.rows, w, X, y)
        if ref.fmt == CSR:
            return _ell_objective_jit(problem, w, X, y)
        return _objective_jit(problem, w, X, y)

    with tracer.span("init", DRIVER):
        # nothing else may hold a cell's initial state: the resident
        # engines do not donate it, so a second reference would keep it
        # on the device for the whole run
        states, done0s = map(list, zip(*(_resume_state(p, r)
                                         for p, r in zip(plans, resumes))))
        done0 = done0s[0]
        if sharded:
            # solver state rides the mesh replicated: a fresh (or resumed)
            # state on the default device would force jit to re-specialize
            # against the committed corpus shardings
            # lint: allow[REPRO002] state placement
            states = [jax.device_put(
                st, NamedSharding(spec.mesh, PartitionSpec()))
                for st in states]

    if all(r is None for r in resumes):
        # compile (epoch fn, embedded snapshot refresh, objective) untimed;
        # a resumed call reuses the original call's jit cache, and paying a
        # full warmup epoch per segment would double the device work of
        # epoch-at-a-time drivers like benchmarks/erm_convergence.py
        with tracer.span("warmup", DRIVER):
            for i, p in enumerate(plans):
                dummy = init_state(p.cfg.solver,
                                   jnp.zeros(ref.features, jnp.float32), m)
                if sharded:
                    # match the live state's sharding or the warmup
                    # compiles a throwaway specialization
                    # lint: allow[REPRO002] warmup placement
                    dummy = jax.device_put(
                        dummy, NamedSharding(spec.mesh, PartitionSpec()))
                jax.block_until_ready(
                    fns[i](dummy, X, y, jax.random.PRNGKey(1)).w)
                jax.block_until_ready(obj(i, states[i].w))

    # which fused kernel the epochs run, and the row DMAs an RS epoch's
    # fused_grad_rows calls issue (counters fused.row_dmas / _bytes); fused
    # plans never share a corpus, so ref is the only cell.  An ELL corpus
    # runs the eager gather/scatter epoch (``kernel=ell``).
    kernel_attr, row_dmas, row_dma_bytes = {}, 0, 0
    if ref.fmt == CSR:
        kernel_attr = {"kernel": "ell"}
    elif ref.kernel == FUSED:
        kernel_attr = {"kernel": batch_access(ref.scheme_name)}
        if kernel_attr["kernel"] == "rows":
            row_dmas, row_dma_bytes = fused_row_dmas(
                ref.cfg, m, spec.batch_size, ref.features)

    # the epoch key schedule is pure in (seed, epoch index): replaying the
    # splits makes a resumed run use the batch schedule the uninterrupted
    # run would have used; every cell shares it (same seed)
    key = jax.random.PRNGKey(spec.seed)
    for _ in range(done0):
        key, _ = jax.random.split(key)

    def objective(i, epoch):
        with tracer.span("objective", DRIVER, epoch=epoch):
            return float(obj(i, states[i].w))

    # the trace is cumulative across resumes: prepending the resumed-from
    # history makes any chain of segments read like one uninterrupted run
    prefixes = [[] if r is None else [float(h) for h in r.history]
                for r in resumes]
    histories: List[List[float]] = [[] for _ in plans]
    compute_s = [0.0] * S
    train_s = 0.0
    rcks = [_RunCheckpointer(p, done0, epochs, t)
            for p, t in zip(plans, tracers)]
    try:
        for e in range(epochs):
            key, sub = jax.random.split(key)
            # each cell's epoch is ONE device call, so its compute span is
            # its epoch; VectorizedLS trial ladders run fused inside the
            # jit, so the span carries the step rule as an attribute and
            # the ladder count lands on the ls.invocations counter below
            with tracer.timespan("epoch", EPOCH, epoch=done0 + e) as se:
                for i, p in enumerate(plans):
                    with tracers[i].timespan("resident_epoch", COMPUTE,
                                             epoch=done0 + e,
                                             step_rule=p.step_rule,
                                             **kernel_attr) as sp:
                        states[i] = fns[i](states[i], X, y, sub)
                        jax.block_until_ready(states[i].w)
                    compute_s[i] += sp.dur
            train_s += se.dur
            for t, p in zip(tracers, plans):
                if row_dmas:
                    t.metrics.counter("fused.row_dmas").inc(row_dmas)
                    t.metrics.counter("fused.row_dma_bytes").inc(
                        row_dma_bytes)
                if p.cfg.step_mode == LINE_SEARCH:
                    t.metrics.counter("ls.invocations").inc(m)
            if spec.data.kind != ARRAYS and e > 0:
                # every epoch after the first of THIS call would have
                # restaged the corpus (a resumed call pays its own staging,
                # so its first epoch saved nothing — crediting per-call
                # keeps split runs' totals consistent with their actual
                # staging count)
                stats.record_h2d_saved(h2d_dt)
            for i, p in enumerate(plans):
                if p.spec.record_objective:
                    histories[i].append(objective(i, done0 + e))
                rcks[i].after_epoch(
                    e, states[i], {"scheme": ref.scheme_name,
                                   "seed": spec.seed,
                                   "epochs": done0 + e + 1},
                    prefixes[i] + histories[i], _cell_stats(stats, S))
    finally:
        for rck in rcks:
            rck.finish()

    return [RunResult(
        plan=p, objective=(histories[i][-1] if histories[i]
                           else objective(i, done0 + epochs - 1)),
        history=np.asarray(prefixes[i] + histories[i]),
        w=np.asarray(states[i].w), solver_state=states[i],
        sampler_state={"scheme": ref.scheme_name, "seed": spec.seed,
                       "epochs": done0 + epochs},
        epochs_run=epochs, epochs_done=done0 + epochs,
        stats=_cell_stats(stats, S), train_s=train_s / S,
        compute_s=compute_s[i]) for i, p in enumerate(plans)]


# ---- streamed backends -----------------------------------------------------

def _drive_streamed(plans: List[ExecutionPlan],
                    resumes: List[Optional[RunResult]], epochs: int,
                    tracer: Tracer, tracers: List[Tracer]) -> List[RunResult]:
    """The streaming engine under the dense and sparse backends: group the
    pipeline's batch stream into <=K-batch chunks (never crossing an epoch
    boundary — snapshot solvers refresh state between epochs),
    double-buffer them host->device (DeviceStager), and scan each chunk in
    one device call per cell.  The per-epoch objective pass and the
    checkpoints run outside the timers, at every epoch boundary."""
    from ..data import pipeline as pipemod

    ref = plans[0]
    spec = ref.spec
    S = len(plans)
    m, K, n = ref.num_batches, ref.chunk, ref.features
    b = spec.batch_size
    with tracer.span("init", DRIVER):
        states, done0s = map(list, zip(*(_resume_state(p, r)
                                         for p, r in zip(plans, resumes))))
    done0 = done0s[0]
    start_step = done0 * m
    scheme_obj = ref.scheme_obj
    adaptive = scheme_obj.adaptive
    fns = [make_epoch_fn(p.spec.problem, p.cfg, weighted=True) if adaptive
           else make_epoch_fn(p.spec.problem, p.cfg) for p in plans]

    # adaptive schemes: read-ahead is disabled (prefetch=0) so the sampler
    # state is exact at every epoch boundary — observe() feedback and the
    # checkpointed sampler_meta() must see exactly the consumed draws; a
    # resumed adaptive run restores the scheme's learning state (scores /
    # cursor) from the checkpoint's own meta instead of the (seed, step)
    # arithmetic the uniform schemes are rebuilt from.  Adaptive plans
    # never share a stream, so resumes[0] is the only resume.
    smeta = (resumes[0].sampler_state
             if adaptive and resumes[0] is not None else None)
    pcfg = pipemod.PipelineConfig(corpus=spec.data.path, batch_size=b,
                                  sampling=spec.scheme, seed=spec.seed,
                                  prefetch=0 if adaptive else spec.prefetch)
    if ref.fmt == CSR:
        from ..data import sparse
        with tracer.span("init", DRIVER):
            csr = sparse.open_csr_corpus(spec.data.path)
            pipe = sparse.SparsePipeline(pcfg, start_step=start_step,
                                         tracer=tracer, sampler_meta=smeta)
        kmax = ref.kmax if ref.kmax else csr.kmax

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

        def full_grad_at(problem, w, data_term_only=False):
            return jnp.asarray(sparse.csr_full_grad(
                problem, csr, np.asarray(w), data_term_only=data_term_only))

        def eval_objs(pairs):
            return [sparse.csr_objective(problem, csr, np.asarray(w))
                    for problem, w in pairs]

        def block_losses(problem, w):
            means, _ = sparse.csr_block_losses(problem, csr, np.asarray(w),
                                               b)
            return {"block_losses": means}

        objective_args = {}
    else:
        from ..data import dataset
        with tracer.span("init", DRIVER):
            mm, _ = dataset.open_corpus(spec.data.path)
            pipe = pipemod.DataPipeline(pcfg, start_step=start_step,
                                        tracer=tracer, sampler_meta=smeta)

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

        # the objective pass reads the corpus in blocks of a training
        # chunk's rows, under the same byte budget; one pass scores every
        # cell
        block_rows = K * b

        def eval_objs(pairs):
            return _dense_objectives(mm, block_rows, pairs, tracer)

        def block_losses(problem, w):
            # per-BLOCK mean loss in one streamed pass (blocks = the b-row
            # batch slots the contiguous schemes index); numpy margins, no
            # per-block jit calls — the eval chunk does not align with the
            # block grid, so rows are binned by global offset
            from ..data.sparse import _loss_np
            wh = np.asarray(w)
            sums = np.zeros(m, np.float64)
            cnt = np.zeros(m, np.int64)
            lo = 0
            for Xc, yc in _row_chunks():
                per = _loss_np(problem.loss, Xc @ wh, yc)
                blk = (lo + np.arange(Xc.shape[0])) // b
                np.add.at(sums, blk, per)
                np.add.at(cnt, blk, 1)
                lo += Xc.shape[0]
            return {"block_losses": sums / np.maximum(cnt, 1)}

        # what one dense objective pass reads from the memmap
        objective_args = {"rows": ref.rows,
                          "chunks": -(-ref.rows // block_rows),
                          "bytes": ref.rows * mm.shape[1] * mm.itemsize}

    sharded = ref.shards > 1
    if sharded:
        # chunk staging shards the batch axis across the mesh; js (the
        # batch-slot indices) replicates.  The CSR layout never gets here —
        # plan() rejects sharded CSR.
        batch_axes = ((None, "batch", None), (None, "batch"), (None,))
        gather = ref.reduction == GATHER
        rep = NamedSharding(spec.mesh, PartitionSpec())
        # lint: allow[REPRO002] state placement, not corpus staging
        states = [jax.device_put(st, rep) for st in states]
        # warmup chunks go through the same staging put so the epoch fn
        # compiles against the shardings the live chunks will carry
        warm_put = make_staging_put(spec.mesh, batch_axes, gather=gather)
        stage_zeros = lambda k: warm_put(tuple(
            np.zeros(a.shape, a.dtype) for a in
            zeros(k) + (jnp.zeros((k,), jnp.int32),)))
        # the per-epoch objective probe and the snapshot full-grad stream
        # run on the HOST corpus either way; pinning w to host first keeps
        # their arithmetic identical to the single-host backend's
        host_w = np.asarray
    else:
        # weighted (adaptive) engines take a trailing (k,) weight vector
        stage_zeros = lambda k: (zeros(k) + (jnp.zeros((k,), jnp.int32),)
                                 + ((jnp.ones((k,), jnp.float32),)
                                    if adaptive else ()))
        host_w = lambda w: w

    def objectives(cells, epoch):
        """The objective pass after ``epoch`` for the listed cells, as a
        ``driver:objective`` span carrying what a dense pass reads."""
        with tracer.span("objective", DRIVER, epoch=epoch, **objective_args):
            return eval_objs([(plans[i].spec.problem, host_w(states[i].w))
                              for i in cells])

    snapshot_cells = [i for i, p in enumerate(plans)
                      if p.cfg.solver in ("svrg", "saag2")]
    with tracer.span("warmup", DRIVER):
        # compile every chunk shape outside the timed region
        for k in sorted({K, m % K} - {0}):
            for fn, p in zip(fns, plans):
                dummy = init_state(p.cfg.solver, jnp.zeros(n, jnp.float32), m)
                if sharded:
                    # lint: allow[REPRO002] warmup placement
                    dummy = jax.device_put(dummy, rep)
                jax.block_until_ready(fn(dummy, *stage_zeros(k)))
        if ref.fmt != CSR:
            for p, st in zip(plans, states):
                _warm_dense_objective(p.spec.problem, n, ref.rows,
                                      block_rows, host_w(st.w))
        for i in snapshot_cells:
            # the snapshot full-grad stream compiles too — keep it out of
            # epoch 1
            jax.block_until_ready(full_grad_at(
                plans[i].spec.problem, jnp.zeros(n, jnp.float32),
                data_term_only=plans[i].cfg.solver == "saag2"))

    def snapshot(i, st):
        problem, cfg = plans[i].spec.problem, plans[i].cfg
        with tracer.span("snapshot", DRIVER):
            st = epoch_begin(
                problem, cfg, st,
                lambda w: full_grad_at(problem, host_w(w),
                                       data_term_only=cfg.solver == "saag2"))
        # keep every state leaf on the mesh: a default-device snapshot
        # gradient would make the donated epoch call re-specialize
        # lint: allow[REPRO002] snapshot-state mesh placement
        return jax.device_put(st, rep) if sharded else st

    # cumulative trace across resumes, as in the resident path
    prefixes = [[] if r is None else [float(h) for h in r.history]
                for r in resumes]
    histories: List[List[float]] = [[] for _ in plans]
    recording = [i for i, p in enumerate(plans) if p.spec.record_objective]
    compute_s = [0.0] * S
    train_s = 0.0
    rcks = [_RunCheckpointer(p, done0, epochs, t)
            for p, t in zip(plans, tracers)]

    def sampler_state(e):
        if adaptive:
            # the adaptive driver drains exactly m draws per epoch and
            # applies observe() BEFORE the checkpoint, so the scheme's own
            # meta (scores / cursor included) is exact here
            return pipe.sampler_meta()
        # deterministic count of CONSUMED batches — the prefetch producer
        # may have advanced the live sampler a few steps
        return {"scheme": ref.scheme_name, "seed": spec.seed,
                "step": start_step + m * (e + 1)}

    def end_epoch(e):
        """Objective pass and checkpoints after epoch ``e``, untimed."""
        if recording:
            for i, v in zip(recording, objectives(recording, done0 + e)):
                histories[i].append(float(v))
        if adaptive and scheme_obj.wants_feedback:
            with tracer.span("feedback", DRIVER, epoch=done0 + e):
                pipe.observe(block_losses(ref.spec.problem, states[0].w))
        for i in range(S):
            rcks[i].after_epoch(e, states[i], sampler_state(e),
                                prefixes[i] + histories[i],
                                _cell_stats(pipe.stats, S))

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

    def on_epoch(e, st):
        states[0] = st
        end_epoch(e)

    try:
        if adaptive:
            states[0], compute_s[0], train_s = _drive_chunked_adaptive(
                pipe, fns[0], states[0], m=m, K=K, epochs=epochs,
                alloc=alloc, fill=fill,
                snapshot_begin=((lambda st: snapshot(0, st))
                                if snapshot_cells else None),
                on_epoch=on_epoch, tracer=tracer, epoch0=done0,
                step_rule=ref.step_rule)
        else:
            if sharded:
                # mesh-aware staging: each chunk lands sharded on the batch
                # axis (per-device H2D divided by the mesh width); 'gather'
                # mode then reshards to replicated inside the staging
                # thread
                stager = pipemod.DeviceStager(
                    host_chunks(), convert=convert, depth=2,
                    stats=pipe.stats, mesh=spec.mesh, batch_axes=batch_axes,
                    gather=gather, tracer=tracer)
            else:
                stager = pipemod.DeviceStager(
                    host_chunks(), put=_put_blocking, convert=convert,
                    depth=2, stats=pipe.stats, tracer=tracer)
            chunks_iter = iter(stager)
            try:
                for e in range(epochs):
                    # the epoch timespan IS the train_s measurement
                    # (snapshot refresh + chunk waits + device calls; the
                    # end-of-epoch pass stays outside); each cell's device
                    # call on a chunk is its own compute span — the same
                    # dur feeds its compute_s
                    with tracer.timespan("train_epoch", EPOCH,
                                         epoch=done0 + e) as se:
                        for i in snapshot_cells:
                            states[i] = snapshot(i, states[i])
                        done = 0
                        while done < m:
                            args = next(chunks_iter)
                            k = int(args[0].shape[0])
                            for i, p in enumerate(plans):
                                with tracers[i].timespan(
                                        "chunk", COMPUTE, epoch=done0 + e,
                                        first_batch=done,
                                        step_rule=p.step_rule) as sc:
                                    states[i] = fns[i](states[i], *args)
                                    jax.block_until_ready(states[i].w)
                                    sc.set(batches=k)
                                compute_s[i] += sc.dur
                            done += k
                    train_s += se.dur
                    end_epoch(e)
            finally:
                stager.close()
                pipe.close()
    finally:
        for rck in rcks:
            rck.finish()
    for t, p in zip(tracers, plans):
        if p.cfg.step_mode == LINE_SEARCH:
            # the trial ladder runs fused inside the chunk jit (one ladder
            # per batch), so the driver books the invocation count
            t.metrics.counter("ls.invocations").inc(m * epochs)

    finals = [i for i in range(S) if not histories[i]]
    final = (dict(zip(finals, objectives(finals, done0 + epochs - 1)))
             if finals else {})
    return [RunResult(
        plan=p, objective=(histories[i][-1] if histories[i]
                           else float(final[i])),
        history=np.asarray(prefixes[i] + histories[i]),
        w=np.asarray(states[i].w), solver_state=states[i],
        sampler_state=sampler_state(epochs - 1), epochs_run=epochs,
        epochs_done=done0 + epochs,
        stats=_cell_stats(pipe.stats, S), train_s=train_s / S,
        compute_s=compute_s[i]) for i, p in enumerate(plans)]


def _drive_chunked_adaptive(pipe, epoch_fn, state, *, m: int, K: int,
                            epochs: int, alloc: Callable, fill: Callable,
                            snapshot_begin: Optional[Callable],
                            on_epoch: Callable,
                            tracer: Tracer = NULL_TRACER, epoch0: int = 0,
                            step_rule: Optional[str] = None,
                            ) -> Tuple[SolverState, float, float]:
    """The chunk loop of the adaptive schemes, for the one cell their
    plans run.  Returns ``(state, compute_s, train_s)``.

    Differences from the uniform loop in :func:`_drive_streamed`, all
    serving one invariant — the scheme state must be EXACT at every epoch
    boundary:

    * the pipeline yields ``(payload, j, weight)`` triples: the scheme
      chooses the gradient-table slot ``j`` (it is NOT ``step % m``) and
      emits the unbiasedness ``weight`` the weighted epoch engine consumes
      as a trailing ``(k,)`` vector;
    * the :class:`DeviceStager` is scoped to ONE epoch: its producer thread
      may only run ahead within the epoch, so after the epoch's chunks
      drain, the (prefetch=0) pipeline has consumed exactly ``m`` draws —
      the feedback statistics ``on_epoch(e, state)`` hands to
      ``pipe.observe`` then land at a deterministic point in the draw
      stream, and ``pipe.sampler_meta()`` is checkpoint-exact there.
    """
    from ..data import pipeline as pipemod

    def epoch_chunks():
        it = iter(pipe)
        done = 0
        while done < m:
            k = min(K, m - done)
            bufs = alloc(k)
            js = np.empty((k,), np.int32)
            ws = np.empty((k,), np.float32)
            for i in range(k):
                payload, j, w = next(it)
                fill(bufs, i, payload)
                js[i] = j
                ws[i] = w
            yield bufs + (js, ws)
            done += k

    compute_s = 0.0
    train_s = 0.0
    try:
        for e in range(epochs):
            stager = pipemod.DeviceStager(epoch_chunks(), put=_put_blocking,
                                          depth=2, stats=pipe.stats,
                                          tracer=tracer)
            with tracer.timespan("train_epoch", EPOCH,
                                 epoch=epoch0 + e) as se:
                if snapshot_begin is not None:
                    state = snapshot_begin(state)
                done = 0
                for args in stager:
                    with tracer.timespan("chunk", COMPUTE,
                                         epoch=epoch0 + e, first_batch=done,
                                         step_rule=step_rule) as sc:
                        state = epoch_fn(state, *args)
                        jax.block_until_ready(state.w)
                        sc.set(batches=int(args[0].shape[0]))
                    compute_s += sc.dur
                    done += args[0].shape[0]
            stager.close()   # producer joined: the sampler is quiescent
            train_s += se.dur
            on_epoch(e, state)                            # untimed
    finally:
        pipe.close()
    return state, compute_s, train_s


def _put_blocking(host):
    # lint: allow[REPRO002] this IS the DeviceStager put (single-host):
    # the stager books every byte it moves through AccessStats
    return jax.block_until_ready(tuple(jax.device_put(a) for a in host))


# ---------------------------------------------------------------------------
# durable-run restore
# ---------------------------------------------------------------------------

def _plan_from_fingerprint(saved: Dict, directory: Path,
                           meta: Dict) -> ExecutionPlan:
    """Rebuild a runnable plan from a checkpoint's own fingerprint — the
    ``resume_from(dir)`` no-spec path after a crash took the process (and
    its in-memory spec) with it.  Every planner choice the fingerprint
    resolved (placement, kernel, step size, ls mode, chunk) is FORCED so
    the rebuilt plan cannot re-plan differently on different hardware; the
    mesh is not rebuilt — pass an explicit plan to continue sharded.
    """
    if saved.get("data") is None:
        raise ValueError(
            "checkpoint was taken from an in-memory arrays source, which "
            "has no path to reopen — pass the plan explicitly: "
            "resume_from(directory, plan(spec))")
    pol = meta.get("policy", {})
    spec = ExperimentSpec(
        data=DataSource.corpus(saved["data"]),
        loss=saved["loss"], reg=saved["reg"],
        solver=saved["solver"],
        # rebuild the Scheme OBJECT: a bare name would silently drop the
        # adaptive schemes' parameters (ema/floor/min_frac) on crash-resume
        scheme=schemes.from_meta({"scheme": saved["scheme"],
                                  "params": saved.get("scheme_params")}),
        step_mode=saved["step_mode"], step_size=saved["step_size"],
        ls_mode=saved["ls_mode"], ls_shrink=saved["ls_shrink"],
        ls_c=saved["ls_c"], ls_max_iter=saved["ls_max_iter"],
        batch_size=saved["batch_size"], epochs=saved["epochs"],
        seed=saved["seed"], record_objective=saved["record_objective"],
        placement=saved["placement"], kernel=saved["kernel"],
        chunk=saved["chunk"],
        checkpoint=CheckpointPolicy(directory, **pol))
    return plan(spec)


def resume_from(directory, plan_: Optional[ExecutionPlan] = None, *,
                step: Optional[int] = None) -> RunResult:
    """Reconstruct a resumable :class:`RunResult` from an on-disk
    checkpoint directory — the crash-recovery entry point.

    With ``plan_=None`` the plan itself is rebuilt from the checkpoint's
    fingerprint (corpus-backed, single-host — the common restart) and is
    available as ``result.plan``.  Passing an explicit ``plan_`` validates
    the checkpoint against it field by field and enables ELASTIC restore:
    a ``reduction='gather'`` sharded checkpoint restores onto a plan with
    a different mesh width — or none — because that whole family is
    bit-identical; ``'psum'`` checkpoints are mesh-pinned and only restore
    onto the identical mesh.  ``step`` picks a specific snapshot (default:
    newest COMPLETE one; a half-deleted step dir is skipped).

    The returned result carries the restored solver pytree, the exact
    two-integer sampler state, and the cumulative objective trace — pass
    it straight back: ``execute(result.plan, resume=result)``.
    """
    directory = Path(directory)
    if not directory.exists():
        # Checkpointer.__init__ would mkdir it — probe BEFORE constructing
        # so a typo'd path fails loudly instead of materializing
        raise FileNotFoundError(f"no checkpoint directory at {directory}")
    ck = Checkpointer(directory)
    step_, meta = ck.read_meta(step)
    saved = meta["plan"]
    if plan_ is None:
        plan_ = _plan_from_fingerprint(saved, directory, meta)
    _validate_fingerprint(saved, plan_)

    # a fresh init state has the saved pytree's exact structure — the
    # restore template; sharded plans restore replicated onto the CURRENT
    # mesh (this is the elastic path: the saving mesh may have been wider,
    # narrower, or absent)
    template = init_state(plan_.cfg.solver,
                          jnp.zeros(plan_.features, jnp.float32),
                          plan_.num_batches)
    shardings = None
    if plan_.shards > 1:
        from ..distributed.sharding import replicated_shardings
        shardings = replicated_shardings(template, plan_.spec.mesh)
    state, meta = ck.restore(template, step=step_, shardings=shardings)

    from ..data import pipeline as pipemod
    fields = {f.name for f in dataclasses.fields(pipemod.AccessStats)}
    stats = pipemod.AccessStats(**{k: v for k, v in meta["stats"].items()
                                   if k in fields})
    history = [float(h) for h in meta["history"]]
    objective = (float(meta["objective"])
                 if meta.get("objective") is not None else float("nan"))
    return RunResult(
        plan=plan_, objective=objective, history=np.asarray(history),
        w=np.asarray(state.w), solver_state=state,
        sampler_state=meta["sampler_state"],
        epochs_run=0, epochs_done=meta["epochs_done"], stats=stats,
        train_s=0.0, compute_s=0.0)
