"""stall_s_per_epoch (epoch engines, streamed): seconds of the traced
job's ``train_epoch`` spans not covered by the device calls inside them,
over its epochs: time the engine waited for staged chunks."""


def read(rec):
    tl = None if rec.traced is None else rec.traced.timeline
    if tl is None:
        return None
    epochs = [e.dur for e in tl.events if e.name == "train_epoch"]
    if not epochs:
        return None
    inside = sum(e.dur for e in tl.events
                 if e.lane == "compute" and e.parent == "train_epoch")
    return (sum(epochs) - inside) / rec.epochs
