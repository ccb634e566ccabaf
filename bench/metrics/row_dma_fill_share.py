"""row_dma_fill_share (kernels, kernels/fused_erm.py): 100 x the bytes of
the sampled rows (counter ``fused.row_dmas`` times n x 4; the labels are
gathered apart from the groups) over the bytes of the aligned 8-row groups
the per-row kernel DMAs to fetch them (``fused.row_dma_bytes``), in the
traced job, in %; resident RS cells only."""
from harness import work


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    counters = {} if tl is None else tl.metrics.get("counters", {})
    moved = counters.get("fused.row_dma_bytes")
    if not moved:
        return None
    n = rec.cell.config["corpus"]["features"]
    return 100.0 * counters.get("fused.row_dmas", 0) * n * work.F32 / moved
