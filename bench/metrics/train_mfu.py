"""train_mfu (device): the traced job's required work at the chip's
peaks (the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s,
counted from shapes in harness/work.py) over its wall time on all its
chips, in %.  Linear ERM is bound by bytes."""


def read(rec):
    if rec.traced is None or rec.peaks is None or rec.traced.error:
        return None
    least = rec.job_work().seconds(rec.peaks)
    return 100.0 * least / (rec.traced.wall_s * rec.cell.chips)
