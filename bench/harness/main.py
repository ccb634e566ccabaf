"""One run of one cell: set-up, the measured window, the check, the result.

1. Set-up: generate (or reuse) the corpus from the seed, then run one warm
   job of one epoch, which compiles every program the window uses.
2. Window: whole jobs back to back for ``seconds``, the last one finished.
   With ``trace`` the first of them runs under the JAX profiler and with the
   program's span timeline on.
3. Check: one job of the window, drawn from the seed, against the plain
   reference.
4. Metrics: each metric the cell reports is read by its own module from
   the run's ``Record``.
"""
from __future__ import annotations

import dataclasses
import math
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

from . import check, corpus, devtrace, jobs, work
from .spec import BENCH, Cell, metric_reader

CACHE = BENCH / "_cache"        # corpora and JAX's compilation cache
OUT = BENCH / "_out"            # the traced run's profile
TRACE_BUFFER = 1 << 21          # spans the traced job may record


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Record:
    """What a metric reader reads."""
    cell: Cell
    seed: int
    corpus: Path
    setup_s: float
    jobs: List[jobs.Job]
    elapsed_s: float
    memory: List[Dict]                       # memory_stats() per chip
    traced: Optional[jobs.Job] = None        # the profiled job
    device: Optional[devtrace.DeviceTrace] = None
    peaks: Optional[Dict] = None

    @property
    def epochs(self) -> int:
        return int(self.cell.traffic["epochs"])

    def lanes(self) -> Dict[str, float]:
        """The traced job's span seconds per lane of the program."""
        return self.traced.timeline.lane_totals()

    def job_work(self) -> work.Work:
        return work.job(self.cell.config, self.cell.traffic, self.corpus)


class CompileCounter:
    """Backend compilations, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def use_compile_cache(directory: Path) -> None:
    import jax
    directory.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(directory))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(cell: Cell, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    return devs[:cell.chips]


def _peak(stats: Dict) -> int:
    """Peak bytes of one chip: live buffers plus the temporaries that
    executables reserve apart from them."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def _spans(job: jobs.Job) -> List:
    """Host spans of a traced job on ``perf_counter`` seconds: the
    benchmark's plan and execute, and every span of the program (its
    tracer starts as ``execute`` is entered)."""
    out = [("plan", job.t0, job.t_execute),
           ("execute", job.t_execute, job.t0 + job.wall_s)]
    if job.timeline is not None:
        out += [(f"{ev.lane}:{ev.name}", job.t_execute + ev.ts,
                 job.t_execute + ev.ts + ev.dur)
                for ev in job.timeline.events]
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, cache: Path = CACHE,
             out: Path = OUT, require_tpu: bool = True) -> Dict:
    """The result line of one run, as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    devs = chips(cell, require_tpu)
    peaks = work.peaks(devs[0].device_kind) if require_tpu else None
    use_compile_cache(cache / "jax")
    compiles = CompileCounter()
    path, made = corpus.ensure(cell.config, seed, cache / "corpus")
    if made:
        os.sync()       # no writeback of the new corpus runs into the window
    print(f"# corpus {'generated' if made else 'reused'} at "
          f"{time.perf_counter() - t_start:.3f} s", file=sys.stderr)
    mesh = jobs.make_mesh(cell.traffic)
    # one epoch compiles every program an E-epoch job runs
    warm = jobs.run_job(cell.config, dict(cell.traffic, epochs=1), path,
                        0, seed, mesh)
    if warm.error:
        raise RuntimeError(f"the warm job failed: {warm.error}")
    setup_s = time.perf_counter() - t_start

    captured = {}

    def traced(run):
        with devtrace.capture(out / "trace") as cap:
            job = run(TRACE_BUFFER)
        captured["cap"] = cap
        return job

    before = compiles.count
    done, elapsed = jobs.window(cell.config, cell.traffic, path, seed, mesh,
                                seconds, trace_first=traced if trace
                                else None)
    print(f"# compiles inside the window: {compiles.count - before}",
          file=sys.stderr)
    print(f"# window {elapsed:.3f} s, {len(done)} jobs", file=sys.stderr)
    for j in done:
        print(f"# job {j.index}: wall {j.wall_s:.3f} s, "
              + ", ".join(f"{k} " + " ".join(f"{v:.6g}" for v in vals)
                          for k, vals in j.usage.items()), file=sys.stderr)
    memory = [d.memory_stats() or {} for d in devs]
    rec = Record(cell=cell, seed=seed, corpus=path, setup_s=setup_s,
                 jobs=done, elapsed_s=elapsed, memory=memory, peaks=peaks)
    if trace:
        rec.traced = done[0]
        if rec.traced.timeline is not None and rec.traced.timeline.dropped:
            raise RuntimeError(f"the traced job dropped "
                               f"{rec.traced.timeline.dropped} spans")
        job = rec.traced
        cap = captured["cap"]
        rec.device = devtrace.reduce(devtrace.load(cap.xplane()),
                                     cap.sync_perf_ns,
                                     (job.t0, job.t0 + job.wall_s),
                                     cell.chips)

    failed = [j for j in done if j.error]
    for j in failed:
        print(f"# job {j.index} failed: {j.error}", file=sys.stderr)
    pick = done[jobs.job_seed(seed, 1 << 20) % len(done)]
    if pick.error:
        nums = {k: math.inf for k in check.NUMBERS}
    else:
        w_ref, f_ref = check.replay(cell.config, cell.traffic, path,
                                    pick.seed)
        nums = check.numbers(pick.w, pick.history, w_ref, f_ref)
    ok, rows = check.verdict(nums, cell.checks)

    wanted = cell.metrics_layer if trace else cell.metrics_e2e
    metrics = {}
    for m in wanted:
        value = metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(_peak(s) for s in memory)}
    result = {"correct": bool(ok and not failed), "attempted": len(done),
              "failed": len(failed), "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=rec.device.busy_s, window_s=rec.device.window_s)
        result["breakdown"] = {
            "device_ops": rec.device.top_ops(),
            "idle_gaps": devtrace.attribute_gaps(rec.device,
                                                 _spans(rec.traced))}
    # a number that could not be taken (the job failed) is null: JSON has
    # no infinity
    result["checks"] = {
        r["name"]: {"value": r["value"] if math.isfinite(r["value"])
                    else None, "limit": r["limit"]} for r in rows}
    print(f"# job {pick.index} (seed {pick.seed}) against the reference",
          file=sys.stderr)
    return result
