"""First-class step-determination rules (paper §4.1).

The paper's experimental grid crosses every solver with TWO step rules —
constant step and Armijo backtracking line search on the mini-batch
objective.  Step determination used to live inside ``core.solvers`` as
private ``_armijo*`` helpers welded to materialized dense batches, which is
why the fused device-resident backends were constant-step only.  This
module makes the step rule a subsystem of its own:

* :class:`ConstantStep` — ``cfg.step_size``, verbatim.
* :class:`BacktrackingLS` — the sequential data-dependent ``while_loop``
  (one trial objective per shrink).  Kept as the parity reference; its
  arithmetic is byte-for-byte the pre-refactor ``_armijo_obj``.
* :class:`VectorizedLS` — the geometric trial ladder
  ``eta0 * rho^k, k = 0..K-1`` evaluated in batched objective sweeps
  (rung 0 straight-line, then geometrically growing blocks behind one
  ``cond``), the FIRST rung passing the Armijo test taken by argmax over
  the accept mask.  Same accepted rung as sequential backtracking
  whenever the accepted step lies on the ladder (up to last-ulp rounding
  of the decomposed trial objective near an exact Armijo tie) — but with
  at most one branch instead of a data-dependent loop, so it scans,
  unrolls, and fuses.

Every backend talks to the rules through a :class:`BatchProbe` — two
capabilities a mini-batch can offer:

* ``objective(u)`` — the trial batch objective at weights ``u`` (what the
  sequential search backtracks on);
* ``margins(u)`` — the batch margins ``z = Xb @ u``.

``margins`` is what makes :class:`VectorizedLS` cheap on every backend:
the trial points all lie on one ray ``w - alpha * v``, so
``z(w - alpha v) = z(w) - alpha * z(v)`` — the batch is read TWICE total
(once for ``z(w)``, once for ``z(v)``), never once per trial, and the l2
term folds into three dot products.  Dense eager batches, padded-ELL CSR
batches, and the fused Pallas margin kernels
(:func:`repro.kernels.fused_erm.fused_batch_margins`) all present the same
probe, which is how one rule implementation serves every execution path.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from .erm import ERMProblem

CONSTANT, LINE_SEARCH = "constant", "line_search"
STEP_MODES = (CONSTANT, LINE_SEARCH)

SEQUENTIAL, VECTORIZED = "sequential", "vectorized"
LS_MODES = (SEQUENTIAL, VECTORIZED)


# ---------------------------------------------------------------------------
# what a mini-batch offers a step rule
# ---------------------------------------------------------------------------

class BatchProbe(NamedTuple):
    """The two things a step rule may ask of the current mini-batch.

    ``objective``/``margins`` are pure callables over trial weights; nothing
    is traced until a rule actually calls them, so constructing a probe for
    :class:`ConstantStep` costs nothing.
    """
    objective: Callable[[jax.Array], jax.Array]   # u -> batch objective
    margins: Callable[[jax.Array], jax.Array]     # u -> (b,) z = Xb @ u
    labels: jax.Array                             # yb (b,)
    mean_loss: Callable[[jax.Array, jax.Array], jax.Array]  # (z, y) -> mean
    reg: float


def dense_probe(problem: ERMProblem, Xb: jax.Array,
                yb: jax.Array) -> BatchProbe:
    """Probe over a materialized dense batch (the eager engines)."""
    return BatchProbe(
        objective=lambda u: problem.batch_objective(u, Xb, yb),
        margins=lambda u: Xb @ u,
        labels=yb, mean_loss=problem.mean_margin_loss, reg=problem.reg)


def ell_probe(problem: ERMProblem, cols: jax.Array, vals: jax.Array,
              yb: jax.Array) -> BatchProbe:
    """Probe over a padded-ELL CSR batch (the sparse chunked engine) —
    margins cost O(b * kmax), the corpus is never densified."""
    return BatchProbe(
        objective=lambda u: problem.ell_batch_objective(u, cols, vals, yb),
        margins=lambda u: problem.ell_margins(u, cols, vals),
        labels=yb, mean_loss=problem.mean_margin_loss, reg=problem.reg)


def fused_probe(problem: ERMProblem, X: jax.Array, y: jax.Array, *,
                start: Optional[jax.Array] = None,
                idx: Optional[jax.Array] = None,
                batch_size: Optional[int] = None,
                interpret: Optional[bool] = None) -> BatchProbe:
    """Probe whose margins come from the fused Pallas margin kernels — the
    batch never materializes in HBM, matching the fused gradient pass.

    Pass exactly one of ``start`` (CS/SS contiguous block; needs
    ``batch_size``) or ``idx`` (scattered RS rows), with the same clamping /
    wrap-around semantics as ``fused_batch_grad_data``.  The sequential
    rule's ``objective`` is composed from the same margins kernel, so line
    search stays device-resident in BOTH ls modes.
    """
    from ..kernels import fused_erm  # deferred: keep core import pallas-free

    if (start is None) == (idx is None):
        raise ValueError("pass exactly one of start= (CS/SS) or idx= (RS)")
    if start is not None and batch_size is None:
        raise ValueError("start= (CS/SS block) also requires batch_size=")
    yb = fused_erm.fused_batch_labels(y, start=start, idx=idx,
                                      batch_size=batch_size)
    margins = lambda u: fused_erm.fused_batch_margins(
        X, u, start=start, idx=idx, batch_size=batch_size,
        interpret=interpret)

    def objective(u):
        return fused_erm.fused_batch_objective(
            problem, X, y, u, start=start, idx=idx, batch_size=batch_size,
            interpret=interpret)

    return BatchProbe(objective=objective, margins=margins, labels=yb,
                      mean_loss=problem.mean_margin_loss, reg=problem.reg)


def _ray_objectives(probe: BatchProbe, zw: jax.Array, zv: jax.Array,
                    ww: jax.Array, wv: jax.Array, vv: jax.Array,
                    alphas: jax.Array) -> jax.Array:
    """Batch objective at every point ``w - alphas[k] * v`` of the search
    ray, from its cached margin/norm decomposition — the ONE copy of the
    sweep arithmetic, shared by :func:`trial_objectives` and
    :meth:`VectorizedLS.pick`."""
    zs = zw[None, :] - alphas[:, None] * zv[None, :]
    data = jax.vmap(probe.mean_loss, in_axes=(0, None))(zs, probe.labels)
    reg = 0.5 * probe.reg * (ww - 2.0 * alphas * wv + alphas * alphas * vv)
    return data + reg


def trial_objectives(probe: BatchProbe, w: jax.Array, v: jax.Array,
                     alphas: jax.Array) -> jax.Array:
    """Batch objective at every trial point ``w - alphas[k] * v`` from TWO
    margin evaluations: ``z(w - a v) = z(w) - a z(v)`` and
    ``||w - a v||^2 = w.w - 2a w.v + a^2 v.v``."""
    return _ray_objectives(probe, probe.margins(w), probe.margins(v),
                           jnp.dot(w, w), jnp.dot(w, v), jnp.dot(v, v),
                           alphas)


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

def validate_ls(step_size: float, shrink: float, c: float, max_iter: int):
    """Reject line-search hyperparameters that cannot terminate or cannot
    decrease — raised here (ValueError) for direct ``SolverConfig`` users
    and surfaced as ``PlanError`` by ``experiment.plan``."""
    if not step_size > 0:
        raise ValueError(
            f"line search needs a positive initial step, got {step_size!r}")
    if not 0.0 < shrink < 1.0:
        raise ValueError(
            f"ls_shrink must lie in (0, 1) — a backtracking factor of "
            f"{shrink!r} would never shrink the step")
    if not 0.0 < c < 1.0:
        raise ValueError(f"ls_c (Armijo constant) must lie in (0, 1), "
                         f"got {c!r}")
    if max_iter < 1:
        raise ValueError(f"ls_max_iter must be >= 1, got {max_iter!r}")


class ConstantStep(NamedTuple):
    """Fixed step size (paper default: 1/L)."""
    step_size: float
    needs_probe: bool = False

    def pick(self, probe: Optional[BatchProbe], w: jax.Array, v: jax.Array,
             g: jax.Array) -> jax.Array:
        return jnp.asarray(self.step_size, w.dtype)


class BacktrackingLS(NamedTuple):
    """Sequential Armijo backtracking on the mini-batch objective only
    (paper §4.1: full-dataset line search 'could hurt the convergence ...
    by taking huge time').  Direction is ``-v``; sufficient decrease wrt
    ``<g, v>``.  One trial objective per shrink, inside a data-dependent
    ``while_loop`` — the parity reference for :class:`VectorizedLS`."""
    step_size: float
    shrink: float = 0.5
    c: float = 1e-4
    max_iter: int = 25
    needs_probe: bool = True

    def pick(self, probe: BatchProbe, w: jax.Array, v: jax.Array,
             g: jax.Array) -> jax.Array:
        obj = probe.objective
        f0 = obj(w)
        gv = jnp.dot(g, v)

        def cond(carry):
            alpha, it = carry
            return (obj(w - alpha * v) > f0 - self.c * alpha * gv) \
                & (it < self.max_iter)

        def body(carry):
            alpha, it = carry
            return alpha * self.shrink, it + 1

        alpha0 = jnp.asarray(self.step_size, w.dtype)
        alpha, _ = jax.lax.while_loop(cond, body, (alpha0, 0))
        # If v is not a descent direction on this batch (<g, v> <= 0) the
        # Armijo condition is vacuous and the loop would return the FULL
        # initial step, which can diverge SAG/SAGA early when the gradient
        # table is still cold.  Fall back to the smallest step the search
        # could ever produce.
        alpha_safe = alpha0 * self.shrink ** self.max_iter
        return jnp.where(gv > 0, alpha, alpha_safe)


class VectorizedLS(NamedTuple):
    """Armijo backtracking with the trial ladder evaluated in batched
    sweeps instead of one objective call per shrink.

    The sequential search only ever returns a rung of the geometric ladder
    ``alpha0 * shrink^k, k = 0..max_iter`` (rung ``max_iter`` untested, on
    exhaustion) — so acceptance can be decided from batched objective
    values over the rungs, and the FIRST rung passing the Armijo test
    (argmax over the accept mask) is the identical step the backtracking
    ``while_loop`` would have produced.

    The ladder is evaluated by GALLOPING.  Rung 0 is probed straight-line
    with the DIRECT trial objective — bit-identical arithmetic (and
    identical cost: one pass over the batch) to the sequential search's
    first trial, because with a well-scaled initial step
    acceptance-at-first-trial is the common case and any fixed sweep
    width would just be overhead there.  Only when rung 0 fails does ONE
    ``lax.cond`` enter the batched regime: the margins ``z(v)`` are
    computed once (``z(w)`` is shared with the gradient pass by CSE), and
    the remaining rungs are swept in geometrically growing blocks
    (2, 4, 8, ... — found-masked, unrolled at trace time) at O(b)
    elementwise cost per rung, where the sequential search pays one full
    objective pass per shrink.
    """
    step_size: float
    shrink: float = 0.5
    c: float = 1e-4
    max_iter: int = 25
    needs_probe: bool = True

    def pick(self, probe: BatchProbe, w: jax.Array, v: jax.Array,
             g: jax.Array) -> jax.Array:
        alpha0 = jnp.asarray(self.step_size, w.dtype)
        # repeated multiplication — NOT cumprod (a log-depth associative
        # scan) or shrink**k — so every rung is bit-identical to the value
        # the sequential while_loop would have produced; max_iter is static,
        # the Python loop unrolls at trace time
        rungs = [alpha0]
        for _ in range(self.max_iter):
            rungs.append(rungs[-1] * self.shrink)
        ladder = jnp.stack(rungs)
        gv = jnp.dot(g, v)

        zw = probe.margins(w)
        ww = jnp.dot(w, w)
        f0 = probe.mean_loss(zw, probe.labels) + 0.5 * probe.reg * ww

        # rung 0: the sequential search's first trial, verbatim — full
        # objective at w - alpha0 * v, same ops, same rounding
        acc0 = probe.objective(w - ladder[0] * v) \
            <= f0 - self.c * ladder[0] * gv

        if self.max_iter == 1:
            alpha = jnp.where(acc0, ladder[0], ladder[-1])
        else:
            # doubling blocks over rungs 1..max_iter-1 (static shapes,
            # unrolled): each is one batched margins-decomposed sweep,
            # found-masked so the FIRST accepted rung wins.  z(v) and the
            # ray dots live INSIDE the cond branch: the accept-at-rung-0
            # common case never computes them.
            blocks = []
            start, j = 1, 1
            while start < self.max_iter:
                size = min(2 ** j, self.max_iter - start)
                blocks.append((start, size))
                start += size
                j += 1

            def sweep_tail(_):
                zv = probe.margins(v)
                wv, vv = jnp.dot(w, v), jnp.dot(v, v)

                def accept(alphas: jax.Array) -> jax.Array:
                    f = _ray_objectives(probe, zw, zv, ww, wv, vv, alphas)
                    return f <= f0 - self.c * alphas * gv

                alpha_t = ladder[-1]              # exhaustion rung
                found = jnp.asarray(False)
                for s, sz in blocks:
                    blk = jax.lax.dynamic_slice(ladder, (s,), (sz,))
                    acc = accept(blk)
                    blk_alpha = blk[jnp.argmax(acc)]
                    hit = jnp.any(acc)
                    alpha_t = jnp.where(~found & hit, blk_alpha, alpha_t)
                    found = found | hit
                return alpha_t

            # non-descent batches (gv <= 0) skip the tail sweep: the
            # safeguard below overrides their result anyway, while the
            # sequential reference grinds through all max_iter trials
            alpha = jax.lax.cond(acc0 | (gv <= 0), lambda _: ladder[0],
                                 sweep_tail, None)
        # same non-descent safeguard as the sequential reference — and the
        # same ARITHMETIC (alpha0 * shrink**max_iter, one Python pow): the
        # repeated-multiply ladder[-1] can differ in the last ulp when the
        # shrink's powers aren't exact, and SAG/SAGA hit this branch on
        # every cold-table batch
        alpha_safe = alpha0 * self.shrink ** self.max_iter
        return jnp.where(gv > 0, alpha, alpha_safe)


StepRule = Union[ConstantStep, BacktrackingLS, VectorizedLS]


def from_config(cfg) -> StepRule:
    """Resolve a ``repro.core.solvers.SolverConfig`` to its step rule."""
    if cfg.step_mode == CONSTANT:
        return ConstantStep(cfg.step_size)
    if cfg.step_mode == LINE_SEARCH:
        validate_ls(cfg.step_size, cfg.ls_shrink, cfg.ls_c, cfg.ls_max_iter)
        if cfg.ls_mode == SEQUENTIAL:
            cls = BacktrackingLS
        elif cfg.ls_mode == VECTORIZED:
            cls = VectorizedLS
        else:
            raise ValueError(f"unknown ls_mode {cfg.ls_mode!r}; "
                             f"want one of {LS_MODES}")
        return cls(cfg.step_size, cfg.ls_shrink, cfg.ls_c, cfg.ls_max_iter)
    raise ValueError(f"unknown step mode {cfg.step_mode!r}; "
                     f"want one of {STEP_MODES}")
