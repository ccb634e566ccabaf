"""ell_resident_roofline (kernels, core/solvers.py ``_ell_epoch``): the
least time the traced job's epochs need at the chip's peaks over the device
seconds of its resident ELL epoch programs, in %.

The required work is ``harness/work.py``'s count for a CSR job: each
nonzero a value and a column id read and 4 FLOPs, each row a pointer and a
label, and MBSGD's w read and written once per batch.  At url's width the
last of these bounds it.  The device seconds are the device's busy time
inside the job's ``compute:resident_epoch`` spans with ``kernel=ell``: the
profiler's op line names an operation by its HLO instruction alone
(``%while.4``), not by the ``ell_resident_epoch`` name the program gives its
epoch, and each such span holds one epoch program and its wait."""


def _epoch_spans(rec):
    tl = rec.traced.timeline
    return [(rec.traced.t_execute + e.ts, rec.traced.t_execute + e.ts + e.dur)
            for e in tl.events
            if (e.lane, e.name) == ("compute", "resident_epoch")
            and e.args.get("kernel") == "ell"]


def _busy_s(trace, spans):
    """Device 0's busy seconds inside the host spans: their length less the
    idle gaps that fall in them."""
    busy = 0.0
    for a, b in spans:
        lo, hi = trace.to_profiler_ns(a), trace.to_profiler_ns(b)
        idle = sum(max(0.0, min(hi, g1) - max(lo, g0))
                   for g0, g1 in trace.gaps)
        busy += (hi - lo - idle) * 1e-9
    return busy


def read(rec):
    if (rec.device is None or rec.peaks is None or rec.traced is None
            or rec.traced.timeline is None or rec.device.gaps is None):
        return None
    spans = _epoch_spans(rec)
    if not spans:
        return None
    busy = _busy_s(rec.device, spans)
    if busy <= 0:
        return None
    return 100.0 * rec.job_work().seconds(rec.peaks) / busy
