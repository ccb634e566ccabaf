"""The mesh reshard under ``reduction='gather'``, on four virtual CPU devices.

``make_staging_put(gather=True)`` turns each staged chunk, sharded over the
mesh on its batch axis, into fully replicated arrays with one compiled
program (all-gathers device to device), never through a host copy.  XLA
fixes the device count at process start, so every case runs in a
subprocess of its own with four forced host devices.
"""
import json

import pytest

from tests.util import run_py

PRELUDE = """
import json
import jax
import numpy as np
# every compile below is a real backend compile: no persistent cache hit
jax.config.update("jax_enable_compilation_cache", False)
"""


def _run(code: str):
    r = run_py(PRELUDE + code, devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.splitlines()[-1])


def test_gather_put_reshards_on_device_and_compiles_once():
    out = _run("""
from jax._src import array as jarray
from repro.distributed import sharding
from repro.obs import Tracer

mesh = jax.make_mesh((4,), ("data",))
axes = ((None, "batch", None), (None, "batch"), (None,))
rng = np.random.default_rng(0)

def chunk(k):
    return (rng.standard_normal((k, 8, 5)).astype(np.float32),
            rng.standard_normal((k, 8)).astype(np.float32),
            np.arange(k, dtype=np.int32))

compiles = [0]
def on_event(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        compiles[0] += 1
jax.monitoring.register_event_duration_secs_listener(on_event)

# every host fetch of a device array goes through ArrayImpl._value
value = jarray.ArrayImpl._value
fetches = [0]
def counted(self):
    fetches[0] += 1
    return value.fget(self)

tracer = Tracer()
live = sharding.make_staging_put(mesh, axes, gather=True, tracer=tracer)
warm = sharding.make_staging_put(mesh, axes, gather=True)
chunks = [chunk(3), chunk(2)]       # an epoch's last chunk is shorter
jarray.ArrayImpl._value = property(counted)
try:
    outs = [live(c) for c in chunks]
    live_fetches, live_compiles = fetches[0], compiles[0]
    again = [warm(c) for c in chunks]
    warm_fetches = fetches[0] - live_fetches
    warm_compiles = compiles[0] - live_compiles
finally:
    jarray.ArrayImpl._value = value

def same(dev, host):
    return all(d.sharding.is_fully_replicated and len(d.sharding.device_set)
               == 4 and d.dtype == h.dtype and np.array_equal(np.asarray(d), h)
               for d, h in zip(dev, host)) and len(dev) == len(host)

print(json.dumps({
    "equal": all(same(o, c) for o, c in zip(outs + again, chunks + chunks)),
    "live_fetches": live_fetches, "warm_fetches": warm_fetches,
    "live_compiles": live_compiles, "warm_compiles": warm_compiles,
    "counters": tracer.metrics.snapshot()["counters"],
    "bytes": int(sum(a.nbytes for c in chunks for a in c)),
}))
""")
    assert out["equal"], out
    assert out["live_fetches"] == 0 and out["warm_fetches"] == 0, out
    # the live puts compiled the reshard for both chunk shapes; a second
    # factory on the same mesh (a job's warm-up put) reuses those programs
    assert out["live_compiles"] >= 2, out
    assert out["warm_compiles"] == 0, out
    # the tracer-less put counts nothing
    assert out["counters"] == {"gather.reshards": 6,
                               "gather.bytes": out["bytes"]}, out


@pytest.mark.parametrize("placement", ["streamed", "resident"])
def test_gather_saga_trajectory_matches_one_device(tmp_path, placement):
    """SAGA under gather on the 4-device mesh follows the one-device
    trajectory bit for bit, and the traced sharded job counts one reshard
    per staged array."""
    corpus = tmp_path / "dense.bin"
    out = _run(f"""
import dataclasses
from repro.api import (DataSource, ExperimentSpec, GATHER, TracePolicy,
                       execute, plan)
from repro.data import dataset

# 1001 rows: neither the 64-row batches nor the mesh width divide it
dataset.synth_erm_corpus({str(corpus)!r}, rows=1001, features=16, seed=5)
base = ExperimentSpec(data=DataSource.corpus({str(corpus)!r}),
                      solver="saga", scheme="systematic", step_size=0.05,
                      batch_size=64, epochs=2, placement={placement!r},
                      chunk=4)
single = execute(plan(base))
p = plan(dataclasses.replace(base, mesh=jax.make_mesh((4,), ("data",)),
                             reduction=GATHER, trace=TracePolicy()))
sharded = execute(p)
counters = sharded.timeline.metrics["counters"]
print(json.dumps({{
    "backend": p.backend, "shards": p.shards,
    "history": list(map(float, single.history)) ==
               list(map(float, sharded.history)),
    "w": bool(np.array_equal(np.asarray(single.w), np.asarray(sharded.w))),
    "reshards": counters.get("gather.reshards", 0),
    "gets": counters.get("stager.gets", 0),
    "bytes": counters.get("gather.bytes", 0),
}}))
""")
    assert out["backend"] == f"sharded-{placement}" and out["shards"] == 4
    assert out["history"] and out["w"], out
    assert out["bytes"] > 0, out
    if placement == "streamed":
        # each staged chunk is (X, y, batch slots): three arrays
        assert out["gets"] > 0 and out["reshards"] == 3 * out["gets"], out
    else:
        assert out["reshards"] == 2, out        # X and y, once per job
