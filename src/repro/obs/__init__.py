"""Zero-dependency tracing + metrics for the access/compute accounting.

Public surface: :class:`Tracer` (span recorder), :data:`NULL_TRACER`
(the disabled default every layer falls back to), :class:`TracePolicy`
(the ``ExperimentSpec.trace`` knob), :class:`Timeline` (the snapshot on
``RunResult.timeline``), the lane constants, and the metrics primitives.
"""
from .metrics import Counter, Histogram, Metrics, NullMetrics
from .trace import (
    ACCESS,
    CHECKPOINT,
    COMPUTE,
    CONVERT,
    DRIVER,
    EPOCH,
    GATHER,
    H2D,
    LANES,
    NULL_TRACER,
    TraceEvent,
    TracePolicy,
    Tracer,
    Timeline,
    WAIT,
)

__all__ = [
    "ACCESS",
    "CHECKPOINT",
    "COMPUTE",
    "CONVERT",
    "DRIVER",
    "EPOCH",
    "GATHER",
    "H2D",
    "LANES",
    "NULL_TRACER",
    "Counter",
    "Histogram",
    "Metrics",
    "NullMetrics",
    "TraceEvent",
    "TracePolicy",
    "Tracer",
    "Timeline",
    "WAIT",
]
