"""Fused epoch-engine tests: kernel parity, chunked-dispatch equivalence,
line-search and pipeline regressions (interpret mode, which checks the
kernels' arithmetic; tests/test_tpu_compile.py checks that they compile)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import samplers, solvers, step_rules
from repro.core.erm import ERMProblem, gather_batch, slice_batch, synth_classification
from repro.core.solvers import SolverConfig
from repro.kernels.fused_erm import (LOSSES, fused_batch_grad,
                                     fused_batch_grad_data, fused_grad_block,
                                     fused_grad_rows)

KEY = jax.random.PRNGKey(0)
L_ROWS, N_FEAT, B = 103, 12, 10          # non-divisible: 103 % 10 != 0


@pytest.fixture(scope="module")
def data():
    X, y, _ = synth_classification(KEY, L_ROWS, N_FEAT)
    w = jax.random.normal(jax.random.PRNGKey(9), (N_FEAT,)) * 0.3
    return X, y, w


# ------------------------------------------------------- kernel parity ----

@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("start", [0, 30, 100])   # 100 clamps to l-b = 93
def test_fused_block_matches_gather_reference(data, loss, start):
    """CS/SS fused gradient == gather_batch + batch_grad, incl. the clamped
    last batch when l % b != 0 (dynamic_slice semantics)."""
    X, y, w = data
    prob = ERMProblem(loss=loss, reg=1e-3)
    g = fused_batch_grad(prob, X, y, w, start=jnp.asarray(start),
                         batch_size=B, interpret=True)
    start_c = min(start, L_ROWS - B)
    Xb, yb = gather_batch(X, y, jnp.arange(start_c, start_c + B))
    ref = prob.batch_grad(w, Xb, yb)
    assert g.shape == ref.shape == (N_FEAT,)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("loss", LOSSES)
def test_fused_rows_matches_gather_reference(data, loss):
    """RS fused gradient == gather_batch + batch_grad for scattered indices
    including duplicates and wrap-around padding indices."""
    X, y, w = data
    prob = ERMProblem(loss=loss, reg=1e-3)
    idx = jnp.asarray([5, 99, 0, 102, 7, 7, 50, 31, 2, 88], jnp.int32)
    g = fused_batch_grad(prob, X, y, w, idx=idx, interpret=True)
    ref = prob.batch_grad(w, *gather_batch(X, y, idx))
    assert g.shape == ref.shape == (N_FEAT,)
    np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("loss", LOSSES)
@pytest.mark.parametrize("scheme", samplers.SCHEMES)
def test_fused_epoch_schedule_parity(data, loss, scheme):
    """Every batch of a full epoch schedule, all 3 schemes x all 3 losses."""
    X, y, w = data
    prob = ERMProblem(loss=loss, reg=1e-3)
    key = jax.random.PRNGKey(4)
    if scheme in (samplers.CYCLIC, samplers.SYSTEMATIC):
        starts = samplers.batch_slice_starts(scheme, key, L_ROWS, B)
        for s in np.asarray(starts):
            g = fused_batch_grad_data(prob, X, y, w, start=jnp.asarray(s),
                                      batch_size=B, interpret=True)
            Xb, yb = slice_batch(X, y, jnp.asarray(s), B)
            ref = prob.batch_grad_data(w, Xb, yb)
            np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)
    else:
        idx_mat = samplers.epoch_indices(scheme, key, L_ROWS, B)
        for j in range(idx_mat.shape[0]):
            g = fused_batch_grad_data(prob, X, y, w, idx=idx_mat[j],
                                      interpret=True)
            ref = prob.batch_grad_data(w, *gather_batch(X, y, idx_mat[j]))
            np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                       rtol=1e-5, atol=1e-6)


def test_fused_grad_low_level_shapes(data):
    X, y, w = data
    gb = fused_grad_block(X, y, w, jnp.asarray(0), loss="logistic",
                          batch_size=B, interpret=True)
    gr = fused_grad_rows(X, y, w, jnp.arange(B, dtype=jnp.int32),
                         loss="logistic", interpret=True)
    assert gb.shape == gr.shape == w.shape
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gr), rtol=1e-5,
                               atol=1e-6)


def test_fused_wrapper_argument_validation(data):
    X, y, w = data
    prob = ERMProblem()
    with pytest.raises(ValueError):
        fused_batch_grad_data(prob, X, y, w)
    with pytest.raises(ValueError):
        fused_batch_grad_data(prob, X, y, w, start=jnp.asarray(0),
                              idx=jnp.arange(4))


# --------------------------------------------- solver-level equivalence ----

@pytest.mark.parametrize("solver", solvers.SOLVERS)
@pytest.mark.parametrize("scheme", samplers.SCHEMES)
def test_fused_run_matches_reference_run(data, solver, scheme):
    """Device-resident run() with use_fused=True == reference gather path."""
    X, y, _ = data
    prob = ERMProblem(reg=1e-3)
    w0 = jnp.zeros(N_FEAT)
    cref = SolverConfig(solver=solver, step_size=0.05)
    wr, _ = solvers.run(prob, cref, scheme, X, y, w0, batch_size=20, epochs=2)
    wf, _ = solvers.run(prob, cref._replace(use_fused=True), scheme, X, y,
                        w0, batch_size=20, epochs=2)
    np.testing.assert_allclose(np.asarray(wr), np.asarray(wf),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("scheme", samplers.SCHEMES)
@pytest.mark.parametrize("ls_mode", [solvers.SEQUENTIAL, solvers.VECTORIZED])
def test_fused_line_search_matches_eager(data, scheme, ls_mode):
    """Line search on the fused path (trial objectives from the fused
    margin kernels) == the eager gather path, both ls modes — the combo
    that used to be rejected as constant-step only."""
    X, y, _ = data
    cfg = SolverConfig(solver=solvers.SVRG, step_mode=solvers.LINE_SEARCH,
                      step_size=1.0, ls_mode=ls_mode)
    w0 = jnp.zeros(N_FEAT)
    we, _ = solvers.run(ERMProblem(reg=1e-3), cfg, scheme, X, y, w0,
                        batch_size=20, epochs=2)
    wf, _ = solvers.run(ERMProblem(reg=1e-3), cfg._replace(use_fused=True),
                        scheme, X, y, w0, batch_size=20, epochs=2)
    np.testing.assert_allclose(np.asarray(we), np.asarray(wf),
                               rtol=1e-5, atol=1e-6)


def test_epoch_fn_rejects_use_fused():
    """The chunked host engine consumes materialized batches; a silently
    ignored use_fused flag would misreport what got benchmarked."""
    with pytest.raises(ValueError, match="use_fused"):
        solvers.make_epoch_fn(ERMProblem(), SolverConfig(use_fused=True))


@pytest.mark.parametrize("solver", solvers.SOLVERS)
@pytest.mark.parametrize("step_mode", [solvers.CONSTANT, solvers.LINE_SEARCH])
def test_chunked_epoch_matches_per_batch_steps(data, solver, step_mode):
    """make_epoch_fn scanning K batches == K make_step_fn calls."""
    X, y, _ = data
    prob = ERMProblem(reg=1e-3)
    cfg = SolverConfig(solver=solver, step_mode=step_mode, step_size=0.05)
    m = 8
    idx = samplers.epoch_indices(samplers.RANDOM, KEY, 80, B)[:m]
    Xc = jnp.stack([X[idx[j]] for j in range(m)])
    yc = jnp.stack([y[idx[j]] for j in range(m)])

    def fresh_state():
        st = solvers.init_state(solver, jnp.zeros(N_FEAT), m)
        if solver in (solvers.SVRG, solvers.SAAG2):
            st = solvers.epoch_begin(prob, cfg, st,
                                     lambda w: prob.full_grad(w, X, y))
        return st

    st_ref = fresh_state()
    step = solvers.make_step_fn(prob, cfg)
    for j in range(m):
        st_ref = step(st_ref, Xc[j], yc[j], jnp.asarray(j))

    epoch_fn = solvers.make_epoch_fn(prob, cfg)
    st_chunk = epoch_fn(fresh_state(), Xc, yc, jnp.arange(m))
    np.testing.assert_allclose(np.asarray(st_ref.w), np.asarray(st_chunk.w),
                               rtol=1e-5, atol=1e-6)
    # second chunk continues from donated state without re-tracing
    assert solvers.make_epoch_fn(prob, cfg) is epoch_fn


def test_epoch_fn_donates_state(data):
    """The passed-in state is consumed (donated) — its buffers are dead."""
    X, y, _ = data
    prob = ERMProblem(reg=1e-3)
    cfg = SolverConfig(step_size=0.05)
    m = 4
    idx = samplers.epoch_indices(samplers.RANDOM, KEY, 40, B)[:m]
    Xc = jnp.stack([X[idx[j]] for j in range(m)])
    yc = jnp.stack([y[idx[j]] for j in range(m)])
    st = solvers.init_state(solvers.MBSGD, jnp.ones(N_FEAT), m)
    out = solvers.make_epoch_fn(prob, cfg)(st, Xc, yc, jnp.arange(m))
    assert out.w.shape == (N_FEAT,)
    assert st.w.is_deleted()


# ------------------------------------------------------- regressions ----

@pytest.mark.parametrize("rule_cls", [step_rules.BacktrackingLS,
                                      step_rules.VectorizedLS])
def test_armijo_non_descent_falls_back_to_small_step(data, rule_cls):
    """<g, v> <= 0 must NOT return the full initial step (divergence risk);
    regression for the silent `return alpha0` fallback — pinned for BOTH
    line-search rules."""
    X, y, _ = data
    prob = ERMProblem(reg=1e-3)
    rule = rule_cls(step_size=1.0)
    probe = step_rules.dense_probe(prob, X[:B], y[:B])
    w = jnp.ones(N_FEAT)
    g = jnp.ones(N_FEAT)
    v = -g                                     # ascent direction: <g, v> < 0
    alpha = rule.pick(probe, w, v, g)
    a_min = rule.step_size * rule.shrink ** rule.max_iter
    assert float(alpha) == pytest.approx(a_min)
    # descent direction still line-searches normally
    alpha2 = rule.pick(probe, w, g, g)
    assert float(alpha2) > a_min
