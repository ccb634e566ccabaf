"""ell_resident_fill_share (ELL convert, data/sparse.py): 100 x the
nonzeros of the traced job's resident ELL corpus (counter
``ell_resident.nonzeros``) over the rows x kmax slots it was padded to
(``ell_resident.slots``), in %; resident CSR cells only."""


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    counters = {} if tl is None else tl.metrics.get("counters", {})
    slots = counters.get("ell_resident.slots")
    if not slots:
        return None
    return 100.0 * counters.get("ell_resident.nonzeros", 0) / slots
