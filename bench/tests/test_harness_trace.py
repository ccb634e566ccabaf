"""The profiler-trace reduction, on a small trace written out by hand in
the profiler's own format: a host plane with the benchmark's sync marker
and two TPU planes whose operations overlap, straddle the window and leave
gaps of known length; and on a trace recorded on a TPU v5e chip
(``data/``, written by ``bench/tools/record_trace_fixture.py``)."""
import importlib.util
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import devtrace, work  # noqa: E402
from harness.spec import find_cell  # noqa: E402

MS = 1_000_000          # ns
SYNC_PERF_NS = 5_000_000_000
# the sync marker sits at 1 ms on the profiler's clock, so profiler time t
# is perf_counter time t + 4.999 s; the window is 2-12 ms on the profiler
WINDOW_PERF_S = (5.001, 5.011)

# (name, start ms, end ms) on the profiler's clock
TPU0 = [("fusion.1", 1.5, 2.5), ("_block_kernel", 3.0, 4.0),
        ("copy.2", 3.5, 4.5), ("fused_grad_block.10", 4.52, 5.0),
        ("fusion.1", 8.0, 9.0), ("fusion.3", 11.5, 13.0)]
TPU1 = [("fusion.1", 2.0, 12.0)]


def _plane(pid, name, line, events):
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    ps = MS * 1000
    evs = " ".join(
        f"events {{ metadata_id: {ids[n]} offset_ps: {round(a * ps)} "
        f"duration_ps: {round((b - a) * ps)} }}" for n, a, b in events)
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in ids.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')


def _profile(planes):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto("\n".join(planes))


HOST = _plane(1, "/host:CPU", "python", [(devtrace.SYNC, 1.0, 1.001)])
DEVICES = [_plane(2, "/device:TPU:0", devtrace.OPS_LINE, TPU0),
           _plane(3, "/device:TPU:1", devtrace.OPS_LINE, TPU1)]


@pytest.fixture(scope="module")
def two():
    return devtrace.reduce(_profile([HOST] + DEVICES), SYNC_PERF_NS,
                           WINDOW_PERF_S, devices=2)


def test_union_of_intervals():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], float)
    assert devtrace._merge(iv).tolist() == [[0, 3], [5, 9], [10, 11]]
    assert devtrace._clip(devtrace._merge(iv), 2, 10).tolist() == [
        [2, 3], [5, 9]]


def test_busy_is_the_union_inside_the_window(two):
    assert two.window_s == pytest.approx(10e-3, rel=1e-9)
    # chip 0: 0.5 + 1.5 + 0.48 + 1 + 0.5 ms; chip 1: the whole window
    assert two.busy_s == pytest.approx((3.98e-3 + 10e-3) / 2, rel=1e-9)
    one = devtrace.reduce(_profile([HOST] + DEVICES), SYNC_PERF_NS,
                          WINDOW_PERF_S, devices=1)
    assert one.busy_s == pytest.approx(3.98e-3, rel=1e-9)
    idle = float(np.sum(one.gaps[:, 1] - one.gaps[:, 0])) * 1e-9
    assert one.busy_s + idle == pytest.approx(one.window_s, rel=1e-9)


def test_kernel_time_is_summed_over_its_calls(two):
    calls, secs = two.kernel(("fused_grad_block", "_block_kernel"))
    assert calls == 2 and secs == pytest.approx(1.48e-3, rel=1e-9)
    assert two.kernel(("_block_kernel",))[0] == 1
    top = dict(two.top_ops())
    # per chip: chip 1's 10 ms and chip 0's 1.5 ms of fusion.1 in the window
    assert top["fusion.1"] == pytest.approx((10e-3 + 1.5e-3) / 2, rel=1e-9)
    assert top["fusion.3"] == pytest.approx(0.5e-3 / 2, rel=1e-9)


def test_gaps_go_to_the_innermost_host_span(two):
    spans = [("plan", 5.001, 5.004), ("execute", 5.004, 5.011),
             ("h2d:stage", 5.0045, 5.0065)]
    rows = dict(devtrace.attribute_gaps(two, spans))
    # gaps 2.5-3, 5-8 and 9-11.5 ms; the h2d span is 5.5-7.5 ms, inside
    # the second gap, whose two ends go to execute
    assert rows == pytest.approx({"plan": 0.5e-3, "h2d:stage": 2.0e-3,
                                  "execute": 3.5e-3,
                                  "between ops (< 50 us)": 0.02e-3},
                                 rel=1e-6)


def test_a_missing_device_or_marker_is_an_error():
    with pytest.raises(ValueError, match="TPU planes"):
        devtrace.reduce(_profile([HOST] + DEVICES), SYNC_PERF_NS,
                        WINDOW_PERF_S, devices=3)
    with pytest.raises(ValueError, match="marker"):
        devtrace.reduce(_profile(DEVICES), SYNC_PERF_NS, WINDOW_PERF_S,
                        devices=2)


DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def chip():
    meta = json.loads((DATA / "tpu_trace.json").read_text())
    trace = devtrace.reduce(devtrace.load(DATA / "tpu_trace.xplane.pb"),
                            meta["sync_perf_ns"],
                            tuple(meta["window_perf_s"]), devices=1)
    return meta, trace


def _roofline_module():
    spec = importlib.util.spec_from_file_location(
        "fused_erm_roofline", BENCH / "metrics" / "fused_erm_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_chip_trace_is_busy_inside_its_window(chip):
    meta, trace = chip
    lo, hi = meta["window_perf_s"]
    assert trace.window_s == pytest.approx(hi - lo, rel=1e-6)
    assert 0 < trace.busy_s < trace.window_s
    idle = float(np.sum(trace.gaps[:, 1] - trace.gaps[:, 0])) * 1e-9
    assert trace.busy_s + idle == pytest.approx(trace.window_s, rel=1e-6)
    # operations are shown by their HLO instruction's name alone
    assert all(" = " not in name for name, _ in trace.top_ops())


def test_the_fused_kernel_is_found_by_its_chip_name(chip):
    """The block kernel's calls carry a name the roofline reader matches:
    one per batch of each epoch, the job's and the program's warm-up
    epoch's, and none for the ops that read its result; its share of the
    roofline is a share."""
    meta, trace = chip
    roofline = _roofline_module()
    calls, secs = trace.kernel(roofline.KERNEL)
    m, epochs = meta["batches_per_epoch"], meta["epochs"]
    assert calls % m == 0 and m * epochs <= calls <= m * (epochs + 1)
    assert secs > 0
    rec = types.SimpleNamespace(
        device=trace, peaks=work.peaks(meta["device_kind"]),
        cell=find_cell("higgs-resident-ss"))
    assert 0 < roofline.read(rec) <= 100
