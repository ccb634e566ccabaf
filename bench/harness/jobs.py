"""What a user runs: one training job, ``plan(spec)`` then ``execute(plan)``
from w = 0, from the corpus on host storage to trained weights.

The cell's configuration fixes the problem, the solver and the batch; its
traffic fixes the sampling scheme, the placement, the kernel, the mesh width
and the epochs of one job.  Each job of a run gets a seed of its own, drawn
from the run's ``--seed``, so its batch schedule differs; every job does the
same amount of work.
"""
from __future__ import annotations

import dataclasses
import gc
import resource
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np


def job_seed(seed: int, index: int) -> int:
    """A 31-bit seed for job ``index`` of a run started with ``seed``."""
    word = np.random.SeedSequence([int(seed), int(index)]).generate_state(1)
    return int(word[0] >> 1)


@dataclasses.dataclass
class Job:
    index: int
    seed: int
    wall_s: float = 0.0
    execute_s: float = 0.0
    t0: float = 0.0                 # perf_counter at plan()
    t_execute: float = 0.0          # perf_counter at execute()
    w: Optional[np.ndarray] = None
    history: Optional[np.ndarray] = None
    timeline: object = None         # the program's Timeline, when traced
    error: str = ""
    # where a slow job's time went: the process's CPU seconds (user,
    # system) and seconds in Python's garbage collector
    usage: Dict[str, tuple] = dataclasses.field(default_factory=dict)


class _GcClock:
    """Seconds in Python's garbage collector, as a ``gc.callbacks`` entry."""

    def __init__(self):
        self.seconds, self._t = 0.0, 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t


def make_mesh(traffic: Dict):
    import jax
    width = int(traffic.get("mesh", 1))
    if width <= 1:
        return None
    return jax.make_mesh((width,), ("data",), devices=jax.devices()[:width])


def make_spec(config: Dict, traffic: Dict, corpus: Path, seed: int, mesh,
              trace_buffer: int = 0):
    from repro.api import DataSource, ExperimentSpec, TracePolicy
    meth, prob = config["method"], config["problem"]
    return ExperimentSpec(
        data=DataSource.corpus(corpus), loss=prob["loss"], reg=prob["reg"],
        solver=meth["solver"], scheme=traffic["scheme"],
        step_mode=meth["step_rule"], batch_size=meth["batch_size"],
        epochs=traffic["epochs"], seed=seed,
        placement=traffic["placement"], kernel=traffic["kernel"],
        mesh=mesh, reduction=traffic.get("reduction", "auto"),
        trace=TracePolicy(buffer=trace_buffer) if trace_buffer else None)


def run_job(config: Dict, traffic: Dict, corpus: Path, index: int,
            run_seed: int, mesh, trace_buffer: int = 0) -> Job:
    """One job; a failure is recorded on the job, not raised."""
    from repro.api import execute, plan
    job = Job(index=index, seed=job_seed(run_seed, index))
    r0 = resource.getrusage(resource.RUSAGE_SELF)
    gc_clock = _GcClock()
    gc.callbacks.append(gc_clock)
    job.t0 = time.perf_counter()
    try:
        p = plan(make_spec(config, traffic, corpus, job.seed, mesh,
                           trace_buffer))
        if p.backend != traffic["backend"]:
            raise RuntimeError(f"planned {p.backend}, the traffic wants "
                               f"{traffic['backend']}: {p.why}")
        job.t_execute = time.perf_counter()
        res = execute(p)
        job.w = np.asarray(res.w)
        job.history = np.asarray(res.history, np.float64)
        job.timeline = res.timeline
        del res
    except Exception as e:          # the boundary: the run reports it
        job.error = f"{type(e).__name__}: {e}"
    finally:
        gc.callbacks.remove(gc_clock)
    t1 = time.perf_counter()
    r1 = resource.getrusage(resource.RUSAGE_SELF)
    job.wall_s = t1 - job.t0
    job.execute_s = t1 - job.t_execute if job.t_execute else 0.0
    d = lambda k: getattr(r1, k) - getattr(r0, k)  # noqa: E731
    job.usage = {"cpu_s": (d("ru_utime"), d("ru_stime")),
                 "gc_s": (gc_clock.seconds,)}
    return job


def window(config: Dict, traffic: Dict, corpus: Path, run_seed: int, mesh,
           seconds: float, trace_first: Optional[callable] = None
           ) -> (List[Job], float):
    """Jobs back to back until ``seconds`` have passed, the one in progress
    finished.  ``trace_first(run)`` wraps the first job (the traced run's
    profiler and span timeline).  Returns (jobs, elapsed seconds); stops at
    the first failed job."""
    jobs: List[Job] = []
    t0 = time.perf_counter()
    while True:
        index = 1 + len(jobs)
        run = lambda buf=0: run_job(config, traffic, corpus, index, run_seed,
                                    mesh, trace_buffer=buf)
        jobs.append(trace_first(run) if trace_first and not jobs else run())
        elapsed = time.perf_counter() - t0
        if jobs[-1].error or elapsed >= seconds:
            return jobs, elapsed
