"""objective_s_per_epoch (objective pass, core/experiment.py): the traced
job's ``driver:objective`` span seconds (the objective over the whole
corpus after each epoch) over its epochs."""


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    if tl is None:
        return None
    secs = [e.dur for e in tl.events
            if e.lane == "driver" and e.name == "objective"]
    return sum(secs) / rec.epochs if secs else None
