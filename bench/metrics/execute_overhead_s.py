"""execute_overhead_s (driver, core/experiment.py execute): the traced
job's time inside execute(), on the benchmark's own span, less the
program's epoch spans.  It holds corpus read and staging, the warm-up
epoch, the per-epoch objective and snapshot passes: what a job pays
outside its training epochs."""


def read(rec):
    if rec.traced is None or rec.traced.timeline is None:
        return None
    return rec.traced.execute_s - rec.lanes().get("epoch", 0.0)
