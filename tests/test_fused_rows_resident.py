"""``execute()`` on the resident fused path against the benchmark's plain
float64 reference (``bench/harness/reference.py``), at a width that is
neither at most 128 nor a multiple of it, so the kernels' lane padding is
live: random sampling runs the per-row DMA kernel ``fused_grad_rows``,
systematic sampling the block kernel.  Interpret mode checks the
arithmetic; ``tests/test_tpu_compile.py`` checks that the kernels compile.

The traced run books, per epoch whose batches run ``fused_grad_rows``, one
row DMA per sampled row (``fused.row_dmas``) and the bytes of the aligned
8-row groups they move at the padded lane width (``fused.row_dma_bytes``).
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

from harness import check, corpus, reference  # noqa: E402

from repro.api import (DataSource, ExperimentSpec, TracePolicy,  # noqa: E402
                       execute, plan)

ROWS, FEATURES, B, EPOCHS = 2000, 200, 64, 2
SEED = 2**31 + 77
# the limits of the cell this path runs in on the chip
LIMITS = json.loads((BENCH / "checks" / "epsilon-resident-rs.json")
                    .read_text())


@pytest.fixture(scope="module")
def corpus_path(tmp_path_factory):
    return corpus.dense_logistic(
        tmp_path_factory.mktemp("eps") / "corpus.bin", 5, rows=ROWS,
        features=FEATURES, separation=2.0)


def _config():
    return {"corpus": {"format": "dense"},
            "problem": {"loss": "logistic", "reg": 1e-4},
            "method": {"solver": "saga", "batch_size": B,
                       "lipschitz_rows": 4096}}


@pytest.mark.parametrize("scheme,kernel", [("random", "rows"),
                                           ("systematic", "block")])
def test_fused_resident_matches_reference(corpus_path, scheme, kernel):
    p = plan(ExperimentSpec(
        data=DataSource.corpus(corpus_path), loss="logistic", reg=1e-4,
        solver="saga", scheme=scheme, batch_size=B, epochs=EPOCHS,
        seed=SEED, placement="resident", kernel="fused",
        trace=TracePolicy(buffer=1 << 12)))
    assert p.backend == "resident-fused"
    res = execute(p)

    data = reference.open_corpus(_config(), corpus_path, reference.REFERENCE)
    w_ref, f_ref = reference.train(
        _config(), {"scheme": scheme, "placement": "resident",
                    "epochs": EPOCHS}, data, SEED)
    nums = check.numbers(res.w, res.history, w_ref, f_ref)
    ok, rows = check.verdict(nums, LIMITS)
    assert ok, rows

    epochs = [ev for ev in res.timeline.events
              if ev.lane == "compute" and ev.name == "resident_epoch"]
    assert len(epochs) == EPOCHS
    assert {ev.args["kernel"] for ev in epochs} == {kernel}
    counters = res.timeline.metrics["counters"]
    if kernel == "rows":
        m = -(-ROWS // B)
        dmas = EPOCHS * m * B                       # one per sampled row
        group = 8 * 256 * 4                         # 200 lanes pad to 256
        assert counters["fused.row_dmas"] == dmas
        assert counters["fused.row_dma_bytes"] == dmas * group
    else:
        assert "fused.row_dmas" not in counters
        assert "fused.row_dma_bytes" not in counters
