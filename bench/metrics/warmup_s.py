"""warmup_s (driver, core/experiment.py execute): the traced job's
``driver:warmup`` span seconds: the compile-and-warm calls execute() makes
before its first epoch."""


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    if tl is None:
        return None
    secs = [e.dur for e in tl.events
            if e.lane == "driver" and e.name == "warmup"]
    return sum(secs) if secs else None
