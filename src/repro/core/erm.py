"""l2-regularized empirical risk minimization (paper §1.1, eq. (2)).

    min_w f(w) = (1/l) sum_i f_i(w) + (C/2) ||w||^2

Losses: logistic (used in the paper's experiments), square, smoothed hinge.
Everything is dense JAX; per-minibatch objective/gradient helpers take either
an index array (scattered access — RS) or a block start (contiguous access —
CS/SS via ``lax.dynamic_slice``), mirroring the two access patterns the paper
compares.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

LOGISTIC = "logistic"
SQUARE = "square"
SMOOTH_HINGE = "smooth_hinge"


def _margin_losses(loss: str) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Per-example loss as a function of (z = w.x, y)."""
    if loss == LOGISTIC:
        # log(1 + exp(-y z)) computed stably
        return lambda z, y: jnp.logaddexp(0.0, -y * z)
    if loss == SQUARE:
        return lambda z, y: 0.5 * (z - y) ** 2
    if loss == SMOOTH_HINGE:
        # quadratically smoothed hinge (keeps Assumption 1 satisfiable)
        def sh(z, y):
            t = y * z
            return jnp.where(t >= 1.0, 0.0,
                             jnp.where(t <= 0.0, 0.5 - t, 0.5 * (1.0 - t) ** 2))
        return sh
    raise ValueError(f"unknown loss {loss!r}")


@dataclasses.dataclass(frozen=True)
class ERMProblem:
    """Static description of an ERM instance. X: (l, n) float, y: (l,) float."""
    loss: str = LOGISTIC
    reg: float = 1e-4          # C in eq. (2)

    # ---- full objective -------------------------------------------------
    def objective(self, w: jax.Array, X: jax.Array, y: jax.Array) -> jax.Array:
        z = X @ w
        per = _margin_losses(self.loss)(z, y)
        return jnp.mean(per) + 0.5 * self.reg * jnp.dot(w, w)

    def full_grad(self, w: jax.Array, X: jax.Array, y: jax.Array) -> jax.Array:
        return jax.grad(self.objective)(w, X, y)

    # ---- mini-batch subproblem (eq. (3)) --------------------------------
    def mean_margin_loss(self, z: jax.Array, yb: jax.Array) -> jax.Array:
        """Mean per-example loss from precomputed margins ``z = Xb @ w``.

        The step-rule subsystem composes trial objectives from margins
        (``z(w - a v) = z(w) - a z(v)``), so this is the loss surface the
        vectorized line search and the fused margin kernels share."""
        return jnp.mean(_margin_losses(self.loss)(z, yb))

    def sum_margin_loss(self, z: jax.Array, yb: jax.Array) -> jax.Array:
        """Summed per-example loss from precomputed margins: the streamed
        objective pass adds these up block by block."""
        return jnp.sum(_margin_losses(self.loss)(z, yb))

    def data_objective(self, w: jax.Array, Xb: jax.Array, yb: jax.Array) -> jax.Array:
        """Loss term only (no regularizer) — SAAG-II treats the reg exactly."""
        return self.mean_margin_loss(Xb @ w, yb)

    def batch_objective(self, w: jax.Array, Xb: jax.Array, yb: jax.Array) -> jax.Array:
        return self.data_objective(w, Xb, yb) + 0.5 * self.reg * jnp.dot(w, w)

    def batch_grad(self, w: jax.Array, Xb: jax.Array, yb: jax.Array) -> jax.Array:
        return jax.grad(self.batch_objective)(w, Xb, yb)

    def batch_grad_data(self, w: jax.Array, Xb: jax.Array, yb: jax.Array) -> jax.Array:
        return jax.grad(self.data_objective)(w, Xb, yb)

    # ---- padded-corpus (masked) variants --------------------------------
    # The sharded 'psum' execution mode pads the corpus with zero rows so it
    # shards evenly across the device mesh.  Zero rows contribute exactly
    # zero to X^T d, but their LOSS at z=0 is not zero — so the full-corpus
    # objective/gradient mask them out and normalize by the TRUE row count.

    def masked_data_objective(self, w: jax.Array, X: jax.Array, y: jax.Array,
                              rows: int) -> jax.Array:
        """Mean data loss over the first ``rows`` rows of a (possibly
        zero-padded) corpus; ``rows`` is static."""
        per = _margin_losses(self.loss)(X @ w, y)
        per = jnp.where(jnp.arange(X.shape[0]) < rows, per, 0.0)
        return jnp.sum(per) / rows

    def masked_objective(self, w: jax.Array, X: jax.Array, y: jax.Array,
                         rows: int) -> jax.Array:
        return (self.masked_data_objective(w, X, y, rows)
                + 0.5 * self.reg * jnp.dot(w, w))

    def masked_full_grad(self, w: jax.Array, X: jax.Array, y: jax.Array,
                         rows: int, data_term_only: bool = False) -> jax.Array:
        g = jax.grad(self.masked_data_objective)(w, X, y, rows)
        return g if data_term_only else g + self.reg * w

    # ---- sparse (padded-ELL) mini-batch, same subproblem ----------------
    # A CSR mini-batch arrives as (cols, vals): (b, kmax) int32/float32 with
    # zero-valued padding (repro.data.sparse.SparseBatch).  The margin is a
    # gather, the gradient a scatter-add — autodiff derives the scatter from
    # the gather, so the five solver update rules need no sparse variants.

    def ell_margins(self, w: jax.Array, cols: jax.Array,
                    vals: jax.Array) -> jax.Array:
        """z_i = x_i . w for padded-ELL rows (padding vals are 0)."""
        return jnp.sum(vals * jnp.take(w, cols), axis=-1)

    def ell_data_objective(self, w: jax.Array, cols: jax.Array,
                           vals: jax.Array, yb: jax.Array) -> jax.Array:
        per = _margin_losses(self.loss)(self.ell_margins(w, cols, vals), yb)
        return jnp.mean(per)

    def ell_batch_objective(self, w: jax.Array, cols: jax.Array,
                            vals: jax.Array, yb: jax.Array) -> jax.Array:
        return (self.ell_data_objective(w, cols, vals, yb)
                + 0.5 * self.reg * jnp.dot(w, w))

    def ell_batch_grad_data(self, w: jax.Array, cols: jax.Array,
                            vals: jax.Array, yb: jax.Array) -> jax.Array:
        return jax.grad(self.ell_data_objective)(w, cols, vals, yb)

    # ---- theory constants (Assumptions 1 & 2) ---------------------------
    def lipschitz(self, X: jax.Array) -> jax.Array:
        """Upper bound on L for the chosen loss: c * max_i ||x_i||^2 + C.

        logistic: c = 1/4, square/smooth_hinge: c = 1.
        """
        c = 0.25 if self.loss == LOGISTIC else 1.0
        row_sq = jnp.sum(X * X, axis=1)
        return c * jnp.max(row_sq) + self.reg

    def strong_convexity(self) -> float:
        """mu >= C (the l2 term guarantees it)."""
        return self.reg


# ---------------------------------------------------------------------------
# The two access patterns the paper compares, as data-selection primitives.
# ---------------------------------------------------------------------------

def gather_batch(X: jax.Array, y: jax.Array, idx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Scattered selection (RS): one gather row per index (~b descriptors)."""
    return jnp.take(X, idx, axis=0), jnp.take(y, idx, axis=0)


@partial(jax.jit, static_argnames=("batch_size",))
def slice_batch(X: jax.Array, y: jax.Array, start: jax.Array,
                batch_size: int) -> Tuple[jax.Array, jax.Array]:
    """Contiguous selection (CS/SS): ONE dynamic_slice (one DMA descriptor)."""
    Xb = jax.lax.dynamic_slice(X, (start, 0), (batch_size, X.shape[1]))
    yb = jax.lax.dynamic_slice(y, (start,), (batch_size,))
    return Xb, yb


def synth_classification(key: jax.Array, l: int, n: int,
                         separation: float = 1.0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Synthetic binary classification data (labels in {-1, +1}).

    Returns (X, y, w_true). Rows are NOT sorted by class: the paper notes
    random shuffling should precede CS/SS when similar points are grouped, so
    the generator interleaves classes the way a pre-shuffled corpus would be.
    """
    k1, k2, k3 = jax.random.split(key, 3)
    w_true = jax.random.normal(k1, (n,)) / jnp.sqrt(n)
    X = jax.random.normal(k2, (l, n))
    logits = separation * (X @ w_true)
    y = jnp.where(jax.random.uniform(k3, (l,)) < jax.nn.sigmoid(logits), 1.0, -1.0)
    return X, y, w_true
