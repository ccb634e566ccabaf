"""chunk_wait_s_per_epoch (epoch engines, data/pipeline.py DeviceStager):
the traced job's ``wait:chunk`` span seconds, the engine blocked at the
stager's queue for the next staged chunk, over its epochs."""


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    if tl is None:
        return None
    secs = [e.dur for e in tl.events
            if e.lane == "wait" and e.name == "chunk"]
    return sum(secs) / rec.epochs if secs else None
