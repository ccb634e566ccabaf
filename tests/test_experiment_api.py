"""ExperimentSpec → plan → execute API tests.

Three contracts:

* the PLANNER selects the documented backend for every
  solver × scheme × dense/sparse × streamed/resident cell, and rejects
  (PlanError, not silent fallback) every combination that cannot run;
* EXECUTION through different backends computes the same optimization
  (streamed vs resident agree on the deterministic cyclic schedule);
* a RunResult RESUMES exactly: executing the budget in two halves
  reproduces the uninterrupted run bit-for-bit, and the sampler state a
  result carries plugs into ``samplers.restore`` (the machinery
  ``tests/test_sampler_resume.py`` property-tests).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AUTO, BACKENDS, EAGER, FUSED, RESIDENT,
                       RESIDENT_EAGER, RESIDENT_FUSED, RESIDENT_SPARSE,
                       SHARDED_RESIDENT,
                       SHARDED_STREAMED, SPARSE_CSR, STREAMED,
                       STREAMED_EAGER, DataSource, ExperimentSpec, PlanError,
                       execute, plan)
from repro.core import samplers, solvers, synth_classification
from repro.core.erm import ERMProblem
from repro.core.solvers import SolverConfig
from repro.data import dataset, sparse
from tests.test_sampler_resume import _stream

ROWS, FEATS, B = 600, 12, 100      # ROWS % B == 0: no wrap-around ambiguity
SFEATS = 64


@pytest.fixture(scope="module")
def dense_corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("api") / "dense.bin"
    dataset.synth_erm_corpus(path, rows=ROWS, features=FEATS, seed=3)
    return path


@pytest.fixture(scope="module")
def csr_corpus(tmp_path_factory):
    path = tmp_path_factory.mktemp("api") / "sparse.csr"
    sparse.synth_sparse_classification(path, rows=ROWS, features=SFEATS,
                                       density=0.05, seed=4)
    return path


@pytest.fixture(scope="module")
def arrays():
    X, y, _ = synth_classification(jax.random.PRNGKey(0), ROWS, FEATS,
                                   separation=2.0)
    return X, y


def _spec(data, **kw):
    kw.setdefault("step_size", 0.05)
    kw.setdefault("batch_size", B)
    kw.setdefault("epochs", 2)
    return ExperimentSpec(data=data, **kw)


# --------------------------------------------------------- planner matrix ----

@pytest.mark.parametrize("scheme", samplers.SCHEMES)
@pytest.mark.parametrize("solver", solvers.SOLVERS)
def test_planner_selects_documented_backend_per_cell(dense_corpus, csr_corpus,
                                                     solver, scheme):
    """Every solver × scheme × dense/sparse × streamed/resident cell lowers
    to exactly the documented backend (or a PlanError for the cells that
    cannot run)."""
    dense = DataSource.corpus(dense_corpus)
    csr = DataSource.corpus(csr_corpus)

    # dense × streamed
    assert plan(_spec(dense, solver=solver, scheme=scheme,
                      placement=STREAMED)).backend == STREAMED_EAGER
    # dense × resident: auto kernel is fused exactly when the backend
    # compiles it natively (TPU); interpret mode stays a parity path
    auto = plan(_spec(dense, solver=solver, scheme=scheme,
                      placement=RESIDENT))
    want = (RESIDENT_FUSED if jax.default_backend() == "tpu"
            else RESIDENT_EAGER)
    assert auto.backend == want
    assert auto.cfg.use_fused == (auto.backend == RESIDENT_FUSED)
    # dense × resident × forced kernels: both honored
    assert plan(_spec(dense, solver=solver, scheme=scheme,
                      placement=RESIDENT, kernel=FUSED)
                ).backend == RESIDENT_FUSED
    assert plan(_spec(dense, solver=solver, scheme=scheme,
                      placement=RESIDENT, kernel=EAGER)
                ).backend == RESIDENT_EAGER
    # sparse × streamed (auto placement lowers to streamed)
    sp = plan(_spec(csr, solver=solver, scheme=scheme))
    assert sp.backend == SPARSE_CSR and sp.cfg.sparse
    # sparse × resident: the corpus staged whole as padded ELL, eager
    # gather/scatter epochs; the fused kernels stay dense-only
    res = plan(_spec(csr, solver=solver, scheme=scheme, placement=RESIDENT))
    assert res.backend == RESIDENT_SPARSE and res.cfg.sparse
    assert res.kernel == EAGER and not res.cfg.use_fused
    with pytest.raises(PlanError, match="dense-only"):
        plan(_spec(csr, solver=solver, scheme=scheme, placement=RESIDENT,
                   kernel=FUSED))


def test_sharded_backends_are_first_class(dense_corpus):
    """The sharded backends are part of the documented backend set, and a
    mesh whose batch axes multiply to one device falls back to the
    single-host backends (the sharded matrix itself lives in
    tests/test_sharded_parity.py under the forced-device-count CI job)."""
    assert SHARDED_STREAMED in BACKENDS and SHARDED_RESIDENT in BACKENDS
    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    p = plan(_spec(DataSource.corpus(dense_corpus), mesh=mesh1))
    assert p.backend in (STREAMED_EAGER, RESIDENT_EAGER, RESIDENT_FUSED)
    assert p.shards == 1 and p.reduction is None


def test_planner_rejects_reduction_without_mesh(dense_corpus):
    with pytest.raises(PlanError, match="mesh"):
        plan(_spec(DataSource.corpus(dense_corpus), reduction="psum"))


def test_planner_auto_placement_small_corpus_is_resident(dense_corpus):
    p = plan(_spec(DataSource.corpus(dense_corpus)))
    assert p.placement == RESIDENT and "fits" in " ".join(p.why)


def test_planner_auto_placement_respects_budget(dense_corpus):
    p = plan(_spec(DataSource.corpus(dense_corpus), resident_budget=1024))
    assert p.placement == STREAMED and p.backend == STREAMED_EAGER


class _Chip:
    """A stand-in TPU device with the given memory_stats()."""
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("stats", [None, {}, {"bytes_limit": 0},
                                   {"bytes_limit": 10_000}])
def test_planner_sizes_a_chip_by_its_own_memory_limit(dense_corpus,
                                                      monkeypatch, stats):
    """Auto placement on a TPU reads bytes_limit; a chip that reports none
    is a PlanError, not an assumed 1 GiB device."""
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: [_Chip(stats)])
    spec = _spec(DataSource.corpus(dense_corpus))
    if not (stats or {}).get("bytes_limit"):
        with pytest.raises(PlanError, match="bytes_limit"):
            plan(spec)
    else:   # 0.6 of 10 kB cannot hold the 31 kB corpus
        assert plan(spec).placement == STREAMED


@pytest.mark.parametrize("rows,want", [(ROWS, RESIDENT_FUSED),
                                       (ROWS + 1, RESIDENT_EAGER)])
def test_planner_keeps_fused_off_a_corpus_the_kernels_would_pad(
        monkeypatch, rows, want):
    """On a TPU the auto kernel is fused only when the corpus needs no
    per-call padding to the kernels' 8-row groups."""
    X, y, _ = synth_classification(jax.random.PRNGKey(0), rows, FEATS)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = plan(_spec(DataSource.arrays(X, y)))
    assert p.backend == want
    assert (want == RESIDENT_FUSED) or any("padded" in w for w in p.why)


def test_planner_line_search_lowers_onto_fused_backend(dense_corpus):
    """step='line_search' is no longer a fused-path conflict: forced fused
    kernels plan RESIDENT_FUSED (trial objectives from the fused margin
    kernels), auto resolves ls_mode to the vectorized trial-ladder sweep,
    and the chosen rule is recorded on the plan/result."""
    p = plan(_spec(DataSource.corpus(dense_corpus), placement=RESIDENT,
                   kernel=FUSED, step_mode="line_search", step_size=1.0))
    assert p.backend == RESIDENT_FUSED
    assert p.cfg.ls_mode == "vectorized" and "[vectorized]" in p.step_rule
    # auto kernel off-TPU still keeps eager (interpret-mode parity path),
    # for the same reason as constant-step cells — not because of the rule
    auto = plan(_spec(DataSource.corpus(dense_corpus), placement=RESIDENT,
                      step_mode="line_search", step_size=1.0))
    want = (RESIDENT_FUSED if jax.default_backend() == "tpu"
            else RESIDENT_EAGER)
    assert auto.backend == want


def test_planner_records_requested_ls_mode(dense_corpus):
    p = plan(_spec(DataSource.corpus(dense_corpus), step_mode="line_search",
                   step_size=1.0, ls_mode="sequential"))
    assert p.cfg.ls_mode == "sequential"
    assert any("sequential" in w for w in p.why)


@pytest.mark.parametrize("kw", [
    dict(ls_shrink=1.0), dict(ls_shrink=0.0), dict(ls_shrink=-0.5),
    dict(step_size=0.0), dict(step_size=-1.0),
    dict(ls_c=0.0), dict(ls_c=1.5), dict(ls_max_iter=0),
    dict(ls_mode="turbo"),
])
def test_plan_rejects_bad_line_search_hyperparameters(dense_corpus, kw):
    """Hyperparameters that cannot terminate or cannot decrease die at
    plan time, not as an endless backtracking loop at run time."""
    with pytest.raises(PlanError):
        plan(_spec(DataSource.corpus(dense_corpus), step_mode="line_search",
                   **{**dict(step_size=1.0), **kw}))


def test_planner_resolves_auto_step_size(dense_corpus, csr_corpus):
    for src in (DataSource.corpus(dense_corpus), DataSource.corpus(csr_corpus)):
        p = plan(ExperimentSpec(data=src, batch_size=B, epochs=1))
        assert 0 < p.cfg.step_size < 1.0          # 1/L for these corpora
    p = plan(ExperimentSpec(data=DataSource.corpus(dense_corpus),
                            step_mode="line_search", batch_size=B, epochs=1))
    assert p.cfg.step_size == 1.0


def test_plan_describe_names_the_decision(dense_corpus):
    p = plan(_spec(DataSource.corpus(dense_corpus), placement=STREAMED))
    text = p.describe()
    assert STREAMED_EAGER in text and str(ROWS) in text


# ------------------------------------------------------------ rejections ----

@pytest.mark.parametrize("kw,match", [
    (dict(kernel=FUSED), "dense-only"),                       # sparse+fused
    # sparse+resident whose padded ELL exceeds the device budget
    (dict(placement=RESIDENT, resident_budget=1024), "resident"),
    # sparse + line_search on the fused path: the combo that used to fall
    # back silently; the CSR conflict is reported first and that's fine —
    # what matters is a clear plan-time rejection
    (dict(kernel=FUSED, step_mode="line_search"), "fused"),
])
def test_plan_rejects_sparse_and_fused_conflicts(csr_corpus, kw, match):
    with pytest.raises(PlanError, match=match):
        plan(_spec(DataSource.corpus(csr_corpus), **kw))


def test_fused_line_search_executes_and_matches_eager(dense_corpus):
    """resident-fused runs line search end-to-end (interpret mode on CPU)
    and agrees with resident-eager on the same plan inputs — the cell the
    planner used to reject."""
    src = DataSource.corpus(dense_corpus)
    kw = dict(solver="saga", scheme="cyclic", epochs=2,
              step_mode="line_search", step_size=1.0)
    r_f = execute(plan(_spec(src, placement=RESIDENT, kernel=FUSED, **kw)))
    r_e = execute(plan(_spec(src, placement=RESIDENT, kernel=EAGER, **kw)))
    assert r_f.plan.backend == RESIDENT_FUSED
    np.testing.assert_allclose(r_f.w, r_e.w, rtol=1e-5, atol=1e-6)


def test_plan_rejects_fused_streamed(dense_corpus):
    with pytest.raises(PlanError, match="materialized"):
        plan(_spec(DataSource.corpus(dense_corpus), placement=STREAMED,
                   kernel=FUSED))


def test_plan_rejects_streamed_arrays(arrays):
    X, y = arrays
    with pytest.raises(PlanError, match="stream"):
        plan(_spec(DataSource.arrays(X, y), placement=STREAMED))


@pytest.mark.parametrize("kw", [
    dict(solver="adam"), dict(scheme="antithetic"), dict(loss="hinge0"),
    dict(step_mode="wolfe"), dict(placement="device"), dict(kernel="triton"),
    dict(batch_size=0), dict(epochs=0),
    dict(batch_size=ROWS + 1),     # used to die as an XLA shape error
])
def test_plan_rejects_unknown_enums_and_bad_budget(dense_corpus, kw):
    with pytest.raises(PlanError):
        plan(_spec(DataSource.corpus(dense_corpus), **kw))


def test_make_step_fn_rejects_use_fused():
    """Regression: the per-batch host step used to silently IGNORE
    use_fused; now it raises (and plan() rejects the combo earlier)."""
    with pytest.raises(ValueError, match="use_fused"):
        solvers.make_step_fn(ERMProblem(), SolverConfig(use_fused=True))


# ---------------------------------------------------- backend equivalence ----

def test_streamed_and_resident_agree_on_cyclic(dense_corpus):
    """CS is deterministic and ROWS % B == 0, so the streamed chunked
    engine and the in-graph resident engine run the identical schedule."""
    src = DataSource.corpus(dense_corpus)
    kw = dict(solver="saga", scheme="cyclic", epochs=3)
    r_s = execute(plan(_spec(src, placement=STREAMED, **kw)))
    r_r = execute(plan(_spec(src, placement=RESIDENT, kernel=EAGER, **kw)))
    np.testing.assert_allclose(r_s.w, r_r.w, rtol=1e-5, atol=1e-6)
    assert abs(r_s.objective - r_r.objective) < 1e-5


@pytest.mark.parametrize("chunk,blocks", [(7, 4), (None, 1)],
                         ids=["short-last-block", "one-block"])
def test_streamed_objective_pass_matches_float64(tmp_path, chunk, blocks):
    """The dense streamed objective pass reads blocks of a training chunk's
    rows (2,550 rows: 3 x 700 + 450 with chunk 7; one block of 2,550 when
    the chunk is the whole epoch), and reads the device once per pass.
    Each history entry is the float64 objective of that epoch's w."""
    from repro.obs import DRIVER, TracePolicy
    rows = 2550
    path = tmp_path / "dense.bin"
    dataset.synth_erm_corpus(path, rows=rows, features=FEATS, seed=5)
    mm, _ = dataset.open_corpus(path)
    X, y = mm[:, :FEATS].astype(np.float64), mm[:, FEATS].astype(np.float64)
    p = plan(_spec(DataSource.corpus(path), placement=STREAMED, chunk=chunk,
                   trace=TracePolicy()))
    res = None
    for epoch in range(3):
        res = execute(p, resume=res, epochs=1)
        w = res.w.astype(np.float64)
        want = (np.mean(np.logaddexp(0.0, -y * (X @ w)))
                + 0.5 * p.spec.problem.reg * w @ w)
        np.testing.assert_allclose(res.history[-1], want, rtol=1e-5)
        assert len(res.history) == epoch + 1
        (span,) = [e for e in res.timeline.events
                   if e.lane == DRIVER and e.name == "objective"]
        assert span.args["chunks"] == blocks
        assert res.timeline.metrics["counters"]["objective.host_syncs"] == 1


def test_history_trace_is_recorded(arrays):
    X, y = arrays
    res = execute(plan(_spec(DataSource.arrays(X, y), epochs=4)))
    assert len(res.history) == 4
    assert res.objective == pytest.approx(res.history[-1])
    assert res.history[-1] < res.history[0]        # it optimizes


# ----------------------------------------------------------------- resume ----

@pytest.mark.parametrize("make_src,placement", [
    ("dense_corpus", STREAMED),
    ("csr_corpus", AUTO),
    ("arrays", AUTO),
], ids=["streamed-dense", "sparse-csr", "resident-arrays"])
def test_runresult_resumes_exactly(request, make_src, placement):
    """Budget in two halves == one uninterrupted run, on every backend."""
    src = request.getfixturevalue(make_src)
    data = (DataSource.arrays(*src) if make_src == "arrays"
            else DataSource.corpus(src))
    kw = dict(solver="mbsgd", scheme="systematic")
    if placement != AUTO:
        kw["placement"] = placement
    p = plan(_spec(data, epochs=4, **kw))
    full = execute(p)
    r1 = execute(p, epochs=2)
    r2 = execute(p, resume=r1, epochs=2)
    np.testing.assert_array_equal(full.w, r2.w)
    assert r2.epochs_done == 4 and r2.epochs_run == 2
    assert full.sampler_state == r2.sampler_state
    # resuming twice from the same result works (state was copied, the
    # donated buffers belong to the engine, not the stored result)
    r2b = execute(p, resume=r1, epochs=2)
    np.testing.assert_array_equal(r2.w, r2b.w)


def test_streamed_sampler_state_plugs_into_restore(dense_corpus):
    """The sampler state a streamed result carries reconstructs the exact
    index stream — the property test_sampler_resume.py pins for
    samplers.restore; here the (seed, step) pair comes from a RunResult."""
    p = plan(_spec(DataSource.corpus(dense_corpus), placement=STREAMED,
                   scheme="random", epochs=2))
    res = execute(p)
    ss = res.sampler_state
    m = p.num_batches
    assert ss["step"] == 2 * m
    want, _ = _stream(samplers.make_sampler(ss["scheme"], ss["seed"], ROWS, B),
                      3 * m)
    got, _ = _stream(samplers.restore(ss["scheme"], ss["seed"], ss["step"],
                                      ROWS, B), m)
    for a, c in zip(want[2 * m:], got):
        np.testing.assert_array_equal(a, c)


def test_resume_rejects_mismatched_backend(dense_corpus, arrays):
    X, y = arrays
    r = execute(plan(_spec(DataSource.arrays(X, y), epochs=1)))
    p_other = plan(_spec(DataSource.corpus(dense_corpus),
                         placement=STREAMED, epochs=1))
    with pytest.raises(ValueError, match="backend"):
        execute(p_other, resume=r)


def test_resume_rejects_same_backend_different_plan(arrays):
    """Same backend is not enough: resuming under a different seed (or any
    spec difference) would silently diverge from an uninterrupted run."""
    X, y = arrays
    r = execute(plan(_spec(DataSource.arrays(X, y), epochs=1)))
    p_seed = plan(_spec(DataSource.arrays(X, y), epochs=1, seed=7))
    assert p_seed.backend == r.plan.backend
    with pytest.raises(ValueError, match="SAME plan"):
        execute(p_seed, resume=r)


def test_resume_rejects_different_arrays(arrays):
    """DataSource equality excludes array payloads, so the resume guard
    must also require the SAME arrays for in-memory sources."""
    X, y = arrays
    r = execute(plan(_spec(DataSource.arrays(X, y), epochs=1)))
    X2 = jnp.array(X)                  # equal content, different buffer
    p2 = plan(_spec(DataSource.arrays(X2, y), epochs=1))
    with pytest.raises(ValueError, match="same arrays"):
        execute(p2, resume=r)


def test_plan_notes_ignored_chunk_under_resident(arrays):
    X, y = arrays
    p = plan(_spec(DataSource.arrays(X, y), chunk=4))
    assert p.chunk == p.num_batches
    assert any("chunk" in w and "ignored" in w for w in p.why)


# -------------------------------------------------------------- RunResult ----

def test_runresult_json_roundtrip(tmp_path, dense_corpus):
    res = execute(plan(_spec(DataSource.corpus(dense_corpus),
                             placement=STREAMED, epochs=1)))
    d = json.loads(json.dumps(res.to_json()))
    assert d["backend"] == STREAMED_EAGER
    assert d["plan"]["solver"] == "mbsgd" and d["plan"]["num_batches"] == 6
    for key in ("objective", "breakdown", "stats", "sampler_state", "w_norm"):
        assert key in d
    assert d["breakdown"]["epoch_s"] > 0
    out = res.save_json(tmp_path / "r.json")
    assert json.loads(out.read_text())["epochs_run"] == 1


def test_fused_backend_executes_and_matches_eager(dense_corpus):
    """resident-fused is a real execution backend (interpret mode on CPU)
    and agrees with resident-eager on the same plan inputs."""
    src = DataSource.corpus(dense_corpus)
    kw = dict(solver="mbsgd", scheme="cyclic", epochs=2)
    r_f = execute(plan(_spec(src, placement=RESIDENT, kernel=FUSED, **kw)))
    r_e = execute(plan(_spec(src, placement=RESIDENT, kernel=EAGER, **kw)))
    assert r_f.plan.backend == RESIDENT_FUSED
    np.testing.assert_allclose(r_f.w, r_e.w, rtol=1e-5, atol=1e-6)
