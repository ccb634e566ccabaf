"""ell_fill_share (ELL convert, data/sparse.py): 100 x the nonzeros the
traced job's ELL batches carried (counter ``ell.nonzeros``) over the
(batch, kmax) slots they were padded to (``ell.slots``), in %; CSR corpora
only."""


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    counters = {} if tl is None else tl.metrics.get("counters", {})
    slots = counters.get("ell.slots")
    if not slots:
        return None
    return 100.0 * counters.get("ell.nonzeros", 0) / slots
