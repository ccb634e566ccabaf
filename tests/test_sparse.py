"""Sparse (CSR) data subsystem tests: on-disk format, LIBSVM ingest, the
synthetic generator, SparsePipeline batch/byte semantics, and the streamed
scipy/numpy-backed full-corpus helpers."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import samplers
from repro.core.erm import ERMProblem
from repro.data import pipeline, sparse
from repro.data.dataset import CorpusMeta

ROWS, FEATS, B = 67, 40, 10          # 67 % 10 != 0: wrap-around exercised
DENSITY = 0.12                       # dense enough that every batch has nnz


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("csr") / "synth.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=3)
    return sparse.open_csr_corpus(path)


# ------------------------------------------------------------ format ----

def test_synth_roundtrip_meta_and_layout(corpus):
    m = corpus.meta
    assert m.kind == sparse.CSR_KIND and m.fmt == "csr"
    assert m.rows == ROWS and m.row_dim == FEATS
    assert m.nnz == int(corpus.indptr[-1]) == len(corpus.values)
    lens = np.diff(corpus.indptr)
    assert m.max_row_nnz == int(lens.max())
    assert lens.min() >= 1
    # paper-like density control (binomial mean, loose tolerance)
    assert abs(corpus.density - DENSITY) < DENSITY
    # row-major sorted column ids within each row
    for i in range(ROWS):
        seg = np.asarray(corpus.indices[corpus.indptr[i]:corpus.indptr[i + 1]])
        assert np.all(np.diff(seg) > 0)
    assert set(np.unique(corpus.labels)) <= {-1.0, 1.0}


def test_corpus_meta_json_back_compat():
    # old dense metadata (no fmt/nnz keys) still parses
    old = CorpusMeta.from_json('{"kind": "rows", "rows": 5, "row_dim": 3, '
                               '"dtype": "float32"}')
    assert old.fmt == "dense" and old.nnz == 0
    new = CorpusMeta.from_json(old.to_json())
    assert new == old
    # dense metas stay byte-compatible with PRE-extension readers
    # (CorpusMeta(**json) there rejects unknown keys): no extension keys
    assert "fmt" not in old.to_json()
    # CSR metas carry them; unknown FUTURE keys are dropped, not fatal
    csr = CorpusMeta("sparse_rows", 5, 3, "float32", fmt="csr", nnz=7,
                     max_row_nnz=2)
    assert CorpusMeta.from_json(csr.to_json()) == csr
    assert CorpusMeta.from_json(
        '{"kind": "rows", "rows": 1, "row_dim": 2, "dtype": "float32", '
        '"some_future_key": 9}').rows == 1


def test_resident_pipeline_refuses_batch_iteration(tmp_path):
    from repro.data import dataset as dense_dataset
    p = tmp_path / "r.bin"
    dense_dataset.synth_erm_corpus(p, rows=40, features=4)
    pipe = pipeline.DataPipeline(pipeline.PipelineConfig(
        corpus=p, batch_size=10, prefetch=0, resident=True))
    with pytest.raises(RuntimeError, match="resident"):
        pipe.read_batch()
    with pytest.raises(RuntimeError, match="resident"):
        next(iter(pipe))
    X, y = pipe.read_all()          # the one sanctioned access
    assert X.shape == (40, 4) and y.shape == (40,)
    assert pipe.stats.bytes_read == X.nbytes + y.nbytes


def test_densify_matches_manual_scatter(corpus):
    X, y = corpus.densify(5, 12)
    assert X.shape == (7, FEATS) and y.shape == (7,)
    r = 8   # absolute row 8 is densified row 3
    s, e = corpus.indptr[8], corpus.indptr[9]
    expect = np.zeros(FEATS, np.float32)
    expect[np.asarray(corpus.indices[s:e])] = corpus.values[s:e]
    np.testing.assert_array_equal(X[3], expect)


def test_open_rejects_dense_meta(tmp_path):
    d = tmp_path / "fake.csr"
    d.mkdir()
    (d / "meta.json").write_text(CorpusMeta("rows", 1, 2, "float32").to_json())
    with pytest.raises(ValueError, match="not a CSR corpus"):
        sparse.open_csr_corpus(d)


# ------------------------------------------------------------ ingest ----

def test_ingest_libsvm_roundtrip(tmp_path):
    src = tmp_path / "toy.libsvm"
    src.write_text(
        "# comment line\n"
        "+1 1:0.5 4:-2.0 7:1.5\n"
        "-1 3:1.0\n"
        "1 7:0.25 2:4.0\n"        # out-of-order indices get sorted
        "-1\n")                    # empty row (all-zero data point)
    meta = sparse.ingest_libsvm(src, tmp_path / "toy.csr")
    assert meta.rows == 4 and meta.row_dim == 7 and meta.nnz == 6
    assert meta.max_row_nnz == 3
    csr = sparse.open_csr_corpus(tmp_path / "toy.csr")
    X, y = csr.densify()
    expect = np.zeros((4, 7), np.float32)
    expect[0, [0, 3, 6]] = [0.5, -2.0, 1.5]
    expect[1, 2] = 1.0
    expect[2, [1, 6]] = [4.0, 0.25]
    np.testing.assert_array_equal(X, expect)
    np.testing.assert_array_equal(y, [1, -1, 1, -1])


def test_ingest_libsvm_zero_based_and_explicit_features(tmp_path):
    src = tmp_path / "zb.libsvm"
    src.write_text("1 0:2.0 2:3.0\n-1 1:1.0\n")
    meta = sparse.ingest_libsvm(src, tmp_path / "zb.csr", features=10,
                                zero_based=True)
    assert meta.row_dim == 10
    X, _ = sparse.open_csr_corpus(tmp_path / "zb.csr").densify()
    assert X.shape == (2, 10)
    assert X[0, 0] == 2.0 and X[0, 2] == 3.0 and X[1, 1] == 1.0


def test_ingest_libsvm_rejects_index_beyond_features(tmp_path):
    src = tmp_path / "bad.libsvm"
    src.write_text("1 5:1.0\n")
    with pytest.raises(ValueError, match="feature index"):
        sparse.ingest_libsvm(src, tmp_path / "bad.csr", features=3)


# ---------------------------------------------------------- pipeline ----

def _cfg(corpus_path, scheme, **kw):
    return pipeline.PipelineConfig(corpus=corpus_path, batch_size=B,
                                   sampling=scheme, seed=0, prefetch=0, **kw)


@pytest.mark.parametrize("scheme", samplers.SCHEMES)
def test_sparse_pipeline_matches_sampler_schedule(tmp_path, scheme):
    path = tmp_path / "p.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=3)
    csr = sparse.open_csr_corpus(path)
    Xd, yd = csr.densify()
    p = sparse.SparsePipeline(_cfg(path, scheme))
    ref = samplers.restore(scheme, 0, 0, ROWS, B)
    for _ in range(9):   # crosses the wrap-around batch and epoch boundary
        batch = p.read_batch()
        idx, ref = samplers.next_batch(ref)
        assert batch.cols.shape == batch.vals.shape == (B, csr.kmax)
        # densify the ELL batch and compare against the dense gather
        got = np.zeros((B, FEATS), np.float32)
        for i in range(B):
            # scatter-ADD: padding (cols=0, vals=0) must not clobber a real
            # column-0 value, so fancy-index assignment won't do
            np.add.at(got[i], batch.cols[i], batch.vals[i])
        np.testing.assert_allclose(got, Xd[idx], rtol=0, atol=0)
        np.testing.assert_array_equal(batch.y, yd[idx])
        assert batch.nnz == int(np.diff(csr.indptr)[idx].sum())


def test_sparse_pipeline_bytes_are_nnz_proportional(tmp_path):
    path = tmp_path / "b.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=3)
    csr = sparse.open_csr_corpus(path)
    p = sparse.SparsePipeline(_cfg(path, samplers.CYCLIC))
    batch = p.read_batch()
    item = csr.indices.itemsize + csr.values.itemsize
    expect = (batch.nnz * item                       # values + indices
              + (B + 1) * csr.indptr.itemsize       # one indptr range
              + B * csr.labels.itemsize)            # labels
    assert p.stats.bytes_read == expect
    # nnz-proportional, NOT the dense b*n footprint
    assert p.stats.bytes_read < B * FEATS * 4
    assert p.stats.read_mb == pytest.approx(expect / 1e6)
    # RS pays per-row indptr lookups instead of one range
    p2 = sparse.SparsePipeline(_cfg(path, samplers.RANDOM))
    b2 = p2.read_batch()
    expect2 = (b2.nnz * item + 2 * B * csr.indptr.itemsize
               + B * csr.labels.itemsize)
    assert p2.stats.bytes_read == expect2


def test_sparse_pipeline_resume_and_state_dict(tmp_path):
    path = tmp_path / "r.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=3)
    p = sparse.SparsePipeline(_cfg(path, samplers.SYSTEMATIC))
    seq = [p.read_batch() for _ in range(6)]
    assert p.state_dict()["step"] == 6
    p2 = sparse.SparsePipeline(_cfg(path, samplers.SYSTEMATIC), start_step=4)
    for k in (4, 5):
        b2 = p2.read_batch()
        np.testing.assert_array_equal(b2.vals, seq[k].vals)
        np.testing.assert_array_equal(b2.cols, seq[k].cols)


def test_sparse_pipeline_prefetch_iter_matches_sync(tmp_path):
    path = tmp_path / "f.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=FEATS,
                                       density=DENSITY, seed=3)
    sync = sparse.SparsePipeline(_cfg(path, samplers.SYSTEMATIC))
    want = [sync.read_batch() for _ in range(5)]
    pre = sparse.SparsePipeline(pipeline.PipelineConfig(
        corpus=path, batch_size=B, sampling=samplers.SYSTEMATIC, seed=0,
        prefetch=2))
    it = iter(pre)
    try:
        for k in range(5):
            got = next(it)
            np.testing.assert_array_equal(got.vals, want[k].vals)
    finally:
        pre.close()


# ------------------------------------------- ELL methods / fallbacks ----

@pytest.fixture(scope="module")
def ell_batch(corpus):
    p_cols = np.zeros((B, corpus.kmax), np.int32)
    p_vals = np.zeros((B, corpus.kmax), np.float32)
    for i in range(B):
        s, e = corpus.indptr[i], corpus.indptr[i + 1]
        k = e - s
        p_cols[i, :k] = corpus.indices[s:e]
        p_vals[i, :k] = corpus.values[s:e]
    return p_cols, p_vals, np.asarray(corpus.labels[:B])


@pytest.mark.parametrize("loss", ["logistic", "square", "smooth_hinge"])
def test_ell_methods_match_dense(corpus, ell_batch, loss):
    cols, vals, yb = ell_batch
    Xd, _ = corpus.densify(0, B)
    prob = ERMProblem(loss=loss, reg=1e-3)
    w = jnp.asarray(np.random.default_rng(0).normal(size=FEATS) * 0.4,
                    jnp.float32)
    np.testing.assert_allclose(
        np.asarray(prob.ell_data_objective(w, cols, vals, yb)),
        np.asarray(prob.data_objective(w, jnp.asarray(Xd), jnp.asarray(yb))),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(prob.ell_batch_grad_data(w, cols, vals, yb)),
        np.asarray(prob.batch_grad_data(w, jnp.asarray(Xd),
                                        jnp.asarray(yb))),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("loss", ["logistic", "square", "smooth_hinge"])
def test_streamed_helpers_match_dense(corpus, loss):
    Xd, yd = corpus.densify()
    prob = ERMProblem(loss=loss, reg=1e-3)
    w = np.random.default_rng(1).normal(size=FEATS).astype(np.float32) * 0.3
    wj, Xj, yj = jnp.asarray(w), jnp.asarray(Xd), jnp.asarray(yd)
    np.testing.assert_allclose(
        sparse.csr_full_grad(prob, corpus, w, chunk=13),
        np.asarray(prob.full_grad(wj, Xj, yj)), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        sparse.csr_full_grad(prob, corpus, w, data_term_only=True, chunk=13),
        np.asarray(prob.batch_grad_data(wj, Xj, yj)), rtol=1e-4, atol=1e-5)
    assert sparse.csr_objective(prob, corpus, w, chunk=13) == pytest.approx(
        float(prob.objective(wj, Xj, yj)), rel=1e-5)
    assert sparse.csr_lipschitz(prob, corpus) == pytest.approx(
        float(prob.lipschitz(Xj)), rel=1e-5)
