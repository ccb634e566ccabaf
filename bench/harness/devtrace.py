"""Reduce a JAX profiler trace to device busy time, kernel time and idle
gaps, on the host clock of the run.

``capture(dir)`` records one traced stretch with host spans on (Python
tracing off, so the trace holds the device's operations and the
``TraceAnnotation`` markers, not every Python call).  Inside it,
``sync()`` opens a ``TraceAnnotation`` at a known ``perf_counter`` time;
``reduce`` finds that marker in the trace and so maps any host time onto
the profiler's clock.

A device is a plane named ``/device:TPU:<i>``; its operations are the
events of its ``XLA Ops`` line.  Busy time is the union of those
intervals inside the window, idle is the rest.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import shutil
import time
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

SYNC = "bench_sync"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Capture:
    dir: Path
    sync_perf_ns: int = 0

    def sync(self) -> None:
        import jax
        self.sync_perf_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(SYNC):
            pass

    def xplane(self) -> Path:
        found = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"want one .xplane.pb under {self.dir}, "
                                    f"found {found}")
        return Path(found[0])


@contextlib.contextmanager
def capture(trace_dir: Path):
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    cap = Capture(trace_dir)
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        cap.sync()
        yield cap
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class DeviceTrace:
    devices: int
    window_s: float
    busy_s: float                     # mean over the devices
    ops: Dict[str, Tuple[int, float]]  # name -> (events, seconds), summed
    gaps: np.ndarray                  # device 0's idle gaps, profiler ns
    offset_ns: float                  # perf_counter ns minus profiler ns

    def to_profiler_ns(self, perf_s: float) -> float:
        return perf_s * 1e9 - self.offset_ns

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` operations with the most time, per device.  A TPU
        trace names an operation by its whole HLO instruction; it is shown
        by the instruction's name alone (``%fusion.164``)."""
        secs: Dict[str, float] = {}
        for name, (_, s) in self.ops.items():
            short = name.split(" = ", 1)[0]
            secs[short] = secs.get(short, 0.0) + s
        rows = sorted(secs.items(), key=lambda kv: -kv[1])[:k]
        return [[name, s / self.devices] for name, s in rows]

    def kernel(self, needles: Sequence[str]) -> Tuple[int, float]:
        """(events, seconds) of the operations whose instruction name holds
        any of ``needles``, summed over the devices.  Only the name counts:
        the rest of a TPU op's HLO text names its operands, and an op that
        reads a kernel's result would match it there."""
        n, s = 0, 0.0
        for name, (cnt, secs) in self.ops.items():
            short = name.split(" = ", 1)[0]
            if any(needle in short for needle in needles):
                n, s = n + cnt, s + secs
        return n, s


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of (start, end) rows, as sorted disjoint rows."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    group_end = np.maximum.reduceat(ends, np.flatnonzero(new))
    return np.stack([starts, group_end], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def load(xplane: Path):
    """The profiler's record of one capture, as ``ProfileData``."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(xplane))


def reduce(pd, sync_perf_ns: int, window_perf_s: Tuple[float, float],
           devices: int) -> DeviceTrace:
    """Busy time, per-operation time and idle gaps of the first
    ``devices`` TPU planes of the trace ``pd`` (``ProfileData``) over the
    host-clock window ``window_perf_s``."""
    sync_ns, planes = None, {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            rest = plane.name[len(DEVICE_PREFIX):]
            if rest.isdigit() and int(rest) < devices:
                planes[int(rest)] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC:
                        sync_ns = ev.start_ns
    if sync_ns is None:
        raise ValueError(f"no {SYNC!r} marker in the trace")
    if len(planes) != devices:
        raise ValueError(f"found TPU planes {sorted(planes)} in the "
                         f"trace, want {devices}")
    offset = sync_perf_ns - sync_ns
    lo, hi = (t * 1e9 - offset for t in window_perf_s)
    ops: Dict[str, List] = {}
    busy, gaps = [], np.zeros((0, 2))
    for i in sorted(planes):
        rows = []
        for line in planes[i].lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, d = ev.start_ns, ev.duration_ns
                if s + d <= lo or s >= hi:
                    continue
                rows.append((s, s + d))
                cnt = ops.setdefault(ev.name, [0, 0.0])
                cnt[0] += 1
                cnt[1] += (min(s + d, hi) - max(s, lo)) * 1e-9
        merged = _clip(_merge(np.asarray(rows, np.float64).reshape(-1, 2)),
                       lo, hi)
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])) * 1e-9)
        if i == 0:
            edges = np.concatenate([[lo], merged.ravel(), [hi]])
            gaps = edges.reshape(-1, 2)
            gaps = gaps[gaps[:, 1] > gaps[:, 0]]
    return DeviceTrace(devices=devices, window_s=(hi - lo) * 1e-9,
                       busy_s=float(np.mean(busy)),
                       ops={k: (v[0], v[1]) for k, v in ops.items()},
                       gaps=gaps, offset_ns=offset)


def attribute_gaps(trace: DeviceTrace,
                   spans: Sequence[Tuple[str, float, float]],
                   k: int = 10, short_s: float = 50e-6) -> List[List]:
    """Idle seconds of device 0 by what the host was doing.  A gap shorter
    than ``short_s`` lies between the operations of one program and is
    booked as such.  A longer one is cut at the edges of the host spans
    (name, start, end in ``perf_counter`` seconds) inside it, and each
    piece goes to the innermost span open over it, or to ``"no host
    span"``: one gap can cover a read, its staging and what follows.
    Returns the ``k`` largest totals."""
    names = [name for name, _, _ in spans]
    a = np.asarray([trace.to_profiler_ns(s) for _, s, _ in spans])
    b = np.asarray([trace.to_profiler_ns(e) for _, _, e in spans])
    dur = (trace.gaps[:, 1] - trace.gaps[:, 0]) * 1e-9
    is_short = dur < short_s
    totals: Dict[str, float] = {}
    if is_short.any():
        totals[f"between ops (< {short_s * 1e6:.0f} us)"] = float(
            dur[is_short].sum())
    for g0, g1 in trace.gaps[~is_short]:
        over = np.flatnonzero((a < g1) & (b > g0))
        ao, bo = a[over], b[over]
        edges = np.unique(np.clip(np.concatenate([[g0, g1], ao, bo]),
                                  g0, g1))
        for p0, p1 in zip(edges[:-1], edges[1:]):
            mid = 0.5 * (p0 + p1)
            open_ = np.flatnonzero((ao <= mid) & (mid <= bo))
            label = ("no host span" if not len(open_) else
                     names[over[open_[np.argmin(bo[open_] - ao[open_])]]])
            totals[label] = totals.get(label, 0.0) + float(p1 - p0) * 1e-9
    return [[n, s] for n, s in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:k]]
