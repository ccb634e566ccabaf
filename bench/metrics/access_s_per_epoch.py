"""access_s_per_epoch (host read, data/pipeline.py, data/sparse.py): the
traced job's ``access`` lane seconds over its epochs."""


def read(rec):
    if rec.traced is None or rec.traced.timeline is None:
        return None
    secs = rec.lanes().get("access")
    return None if secs is None else secs / rec.epochs
