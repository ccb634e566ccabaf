"""``DataPipeline.read_all``: the resident shard as contiguous ``(X, y)``,
copied from the memmap in row blocks over a thread pool.

The blocks are the real ones (``READ_BLOCK_BYTES`` of file each), so the
shards below are sized in blocks: smaller than one, exactly one, and two
and a half.  The result must be bitwise the contiguity copies of the
file's columns, and a resident ``execute()`` on a file corpus must train
exactly as on the same rows handed over as arrays.
"""
import os

import numpy as np
import pytest

from repro.api import DataSource, ExperimentSpec, execute, plan
from repro.data import dataset, pipeline
from repro.obs import ACCESS, Tracer

WIDTHS = [1, 28, 1100]
SHARDS = {"under-a-block": lambda step: step // 3 + 1,
          "one-block": lambda step: step,
          "mid-block": lambda step: 2 * step + step // 2}


def _block_rows(n: int) -> int:
    return max(1, pipeline.READ_BLOCK_BYTES // ((n + 1) * 4))


def _corpus(path, rows: int, n: int, seed: int):
    data = np.random.default_rng(seed).standard_normal(
        (rows, n + 1), dtype=np.float32)
    dataset.write_corpus(path, data, "rows")
    return data


def _read(path, host: int = 0, num_hosts: int = 1):
    tracer = Tracer()
    pipe = pipeline.DataPipeline(pipeline.PipelineConfig(
        corpus=path, batch_size=1, prefetch=0, resident=True, host=host,
        num_hosts=num_hosts), tracer=tracer)
    X, y = pipe.read_all()
    return pipe, tracer, X, y


def _check(pipe, tracer, X, y, shard: np.ndarray):
    rows, n = shard.shape[0], shard.shape[1] - 1
    np.testing.assert_array_equal(X, np.ascontiguousarray(shard[:, :n]))
    np.testing.assert_array_equal(y, np.ascontiguousarray(shard[:, n]))
    for a in (X, y):
        assert a.dtype == np.float32 and a.flags.c_contiguous
    assert X.shape == (rows, n) and y.shape == (rows,)
    assert pipe.stats.bytes_read == shard.nbytes      # the shard's file bytes
    assert pipe.stats.batches == 1

    blocks = -(-rows // _block_rows(n))
    (span,) = [e for e in tracer.timeline().events
               if e.lane == ACCESS and e.name == "read_all"]
    assert span.args["blocks"] == blocks
    assert span.args["bytes"] == shard.nbytes
    assert tracer.metrics.snapshot()["counters"]["read_all.blocks"] == blocks
    if blocks >= 2 and len(os.sched_getaffinity(0)) >= 2:
        assert span.args["workers"] >= 2
    assert 1 <= span.args["workers"] <= min(8, blocks)


@pytest.mark.parametrize("shard", list(SHARDS))
@pytest.mark.parametrize("n", WIDTHS)
def test_read_all_is_the_contiguous_columns(tmp_path, n, shard):
    rows = SHARDS[shard](_block_rows(n))
    data = _corpus(tmp_path / "c.bin", rows, n, seed=n)
    _check(*_read(tmp_path / "c.bin"), data)


@pytest.mark.parametrize("n", WIDTHS)
def test_second_host_reads_its_own_shard(tmp_path, n):
    # host 1 of 2 starts past the file's first row and, at two and a half
    # blocks of file, ends mid-block of its own shard
    rows = 2 * _block_rows(n) + _block_rows(n) // 2 + 1
    data = _corpus(tmp_path / "c.bin", rows, n, seed=100 + n)
    lo, hi = dataset.host_shard(rows, 1, 2)
    assert lo > 0
    _check(*_read(tmp_path / "c.bin", host=1, num_hosts=2), data[lo:hi])


# --------------------------------- execute(): file corpus == in-memory ----

ROWS, FEATS, B = 1024, 40, 64


@pytest.fixture(scope="module")
def file_and_arrays(tmp_path_factory):
    path = tmp_path_factory.mktemp("parity") / "corpus.bin"
    dataset.synth_erm_corpus(path, rows=ROWS, features=FEATS, seed=21)
    mm, _ = dataset.open_corpus(path)
    rows = np.array(mm)
    return path, rows[:, :FEATS], rows[:, FEATS]


@pytest.mark.parametrize("scheme,kernel", [("systematic", "fused"),
                                           ("random", "fused"),
                                           ("random", "eager")])
def test_resident_file_corpus_trains_as_arrays(file_and_arrays, scheme,
                                               kernel):
    path, X, y = file_and_arrays

    def run(data):
        return execute(plan(ExperimentSpec(
            data=data, loss="logistic", reg=1e-4, solver="saga",
            scheme=scheme, batch_size=B, epochs=2, seed=5, step_size=0.05,
            placement="resident", kernel=kernel)))

    a, b = run(DataSource.corpus(path)), run(DataSource.arrays(X, y))
    assert a.plan.backend == b.plan.backend
    np.testing.assert_array_equal(a.w, b.w)
    np.testing.assert_array_equal(a.history, b.history)
    assert a.objective == b.objective
