"""convert_s_per_epoch (ELL convert, data/sparse.py): the traced job's
``convert`` lane seconds over its epochs; CSR corpora only."""


def read(rec):
    if rec.traced is None or rec.traced.timeline is None:
        return None
    secs = rec.lanes().get("convert")
    return None if secs is None else secs / rec.epochs
