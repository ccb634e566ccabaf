"""h2d_s_per_epoch (staging, DeviceStager and distributed/sharding.py):
the traced job's ``h2d`` lane seconds over its epochs."""


def read(rec):
    if rec.traced is None or rec.traced.timeline is None:
        return None
    secs = rec.lanes().get("h2d")
    return None if secs is None else secs / rec.epochs
