"""untraced_s (driver, core/experiment.py execute): seconds of the traced
job's execute(), from its call to the job's end on the benchmark's clock,
covered by no span of the program on any lane.  Spans are placed by the
timeline's ``origin_s`` (its tracer's epoch on ``time.perf_counter``); a
timeline without one gives nothing."""


def read(rec):
    job = rec.traced
    tl = None if job is None else job.timeline
    origin = getattr(tl, "origin_s", None)
    if origin is None:
        return None
    lo, hi = job.t_execute, job.t0 + job.wall_s
    covered, reach = 0.0, lo
    for a, b in sorted((origin + e.ts, origin + e.ts + e.dur)
                       for e in tl.events):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            covered += b - a
            reach = b
    return (hi - lo) - covered
