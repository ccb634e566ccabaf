"""compute_s_per_epoch (epoch engines, core/solvers.py): the traced job's
``compute`` lane seconds (device calls, each ended by block_until_ready)
over its epochs."""


def read(rec):
    if rec.traced is None or rec.traced.timeline is None:
        return None
    secs = rec.lanes().get("compute")
    return None if secs is None else secs / rec.epochs
