"""csr_load_s (host read, data/sparse.py, core/experiment.py): the seconds
the traced job spent bringing its CSR corpus onto the device, once per job:
the ``access:read_csr`` read from the memmaps, the ``convert:ell_resident``
padding into ELL rows and the ``h2d:stage_resident`` put; resident CSR
cells only."""

SPANS = {("access", "read_csr"), ("convert", "ell_resident"),
         ("h2d", "stage_resident")}


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    if tl is None:
        return None
    if not any((e.lane, e.name) == ("access", "read_csr")
               for e in tl.events):
        return None
    return sum(e.dur for e in tl.events if (e.lane, e.name) in SPANS)
