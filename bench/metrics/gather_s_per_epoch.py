"""gather_s_per_epoch (mesh, distributed/sharding.py): the traced job's
``gather`` lane seconds (the reshard of staged chunks to replicated) over
its epochs; meshes only."""


def read(rec):
    if rec.traced is None or rec.traced.timeline is None:
        return None
    secs = rec.lanes().get("gather")
    return None if secs is None else secs / rec.epochs
