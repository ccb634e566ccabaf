"""repro.api — the single import surface for running experiments.

Everything the paper's matrix (and its execution backends) needs is three
names: declare an :class:`ExperimentSpec`, lower it with :func:`plan`, run
it with :func:`execute` (or one-shot :func:`run_experiment`):

    from repro.api import DataSource, ExperimentSpec, run_experiment

    spec = ExperimentSpec(data=DataSource.corpus("corpus.bin"),
                          solver="saga", scheme="systematic", epochs=5)
    result = run_experiment(spec)
    print(result.objective, result.breakdown())

See :mod:`repro.core.experiment` for the planner rules and the
backend-selection matrix.
"""
from .analysis import AuditError, AuditReport, audit  # noqa: F401
from .checkpoint.checkpointer import (  # noqa: F401
    Checkpointer, CheckpointPolicy)
from .core.experiment import (  # noqa: F401
    ARRAYS, AUTO, BACKENDS, CSR, DENSE, EAGER, FUSED, GATHER, LOSSES, PSUM,
    RESIDENT, RESIDENT_EAGER, RESIDENT_FUSED, RESIDENT_SPARSE,
    SHARDED_RESIDENT, SHARDED_STREAMED, SPARSE_CSR, STREAMED, STREAMED_EAGER,
    DataSource, ExecutionPlan, ExperimentSpec, PlanError, RunResult,
    execute, plan, resume_from, run_experiment)
from .core.samplers import CYCLIC, RANDOM, SCHEMES, SYSTEMATIC  # noqa: F401
from .core.schemes import (  # noqa: F401
    ChunkImportance, Cyclic, Random, Scheme, StochasticBatch, Systematic)
from .core.solvers import CONSTANT, LINE_SEARCH, SOLVERS  # noqa: F401
from .core.step_rules import LS_MODES, SEQUENTIAL, VECTORIZED  # noqa: F401
from .core.supercell import (  # noqa: F401
    DEFAULT_MAX_CELLS, CellBatch, coalesce, execute_supercell,
    supercell_key)
from .obs import Timeline, TracePolicy, Tracer  # noqa: F401
from .service import ExperimentService, Outcome, serve  # noqa: F401

__all__ = [
    "ARRAYS", "AUTO", "BACKENDS", "CSR", "DENSE", "EAGER", "FUSED",
    "GATHER", "LOSSES", "PSUM", "RESIDENT", "RESIDENT_EAGER",
    "RESIDENT_FUSED", "RESIDENT_SPARSE", "SHARDED_RESIDENT",
    "SHARDED_STREAMED", "SPARSE_CSR",
    "STREAMED", "STREAMED_EAGER",
    "CYCLIC", "RANDOM", "SCHEMES", "SYSTEMATIC",
    "ChunkImportance", "Cyclic", "Random", "Scheme", "StochasticBatch",
    "Systematic",
    "CONSTANT", "LINE_SEARCH", "SOLVERS",
    "LS_MODES", "SEQUENTIAL", "VECTORIZED",
    "AuditError", "AuditReport", "CellBatch", "Checkpointer",
    "CheckpointPolicy", "DEFAULT_MAX_CELLS", "DataSource", "ExecutionPlan",
    "ExperimentService", "ExperimentSpec", "Outcome", "PlanError",
    "RunResult", "Timeline", "TracePolicy", "Tracer",
    "audit", "coalesce", "execute", "execute_supercell", "plan",
    "resume_from", "run_experiment", "serve", "supercell_key",
]
