"""Data-parallel logistic-regression training on TPU.

Replaces Spark MLlib's ``LogisticRegression.fit`` (the trainer behind the
shipped artifact's final stage; hyperparameters in its metadata: regParam 0.0,
elasticNetParam 0.0, maxIter 100, tol 1e-6, fitIntercept, standardization).
Optimizer is L-BFGS (optax), full-batch like Spark, with the whole loop under
one jit: ``lax.while_loop`` over L-BFGS updates with gradient-norm + relative
objective-change stopping.

Distribution: rows shard over the mesh "data" axis; the loss is a masked mean,
so XLA inserts the cross-chip psum for the reduction — the moral equivalent of
Spark's treeAggregate over executors (and of XGBoost's Rabit allreduce),
riding ICI instead of the JVM shuffle.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from fraud_detection_tpu.models.linear import LogisticRegression
from fraud_detection_tpu.parallel import mesh as mesh_lib


@dataclass
class FitInfo:
    """Convergence record for a training run."""
    final_loss: float
    iterations: int
    max_iter: int

    @property
    def converged(self) -> bool:
        return self.iterations < self.max_iter


def _loss_fn(params, X, y, mask, l2):
    """Masked mean binary logloss (+ optional L2 on weights, not intercept)."""
    w, b = params
    logits = X @ w + b
    per_row = optax.sigmoid_binary_cross_entropy(logits, y) * mask
    loss = jnp.sum(per_row) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + 0.5 * l2 * jnp.sum(w * w)


def _fit_lbfgs_impl(X, y, mask, l2, tol, max_iter: int):
    F = X.shape[1]
    params = (jnp.zeros((F,), X.dtype), jnp.zeros((), X.dtype))
    opt = optax.lbfgs()
    state = opt.init(params)
    loss = lambda p: _loss_fn(p, X, y, mask, l2)
    value_and_grad = optax.value_and_grad_from_state(loss)

    def cond(carry):
        params, state, prev_val, it = carry
        val = optax.tree_utils.tree_get(state, "value")
        grad = optax.tree_utils.tree_get(state, "grad")
        gnorm = optax.tree_utils.tree_norm(grad)
        rel_impr = jnp.abs(prev_val - val) / jnp.maximum(jnp.abs(prev_val), 1e-12)
        not_converged = jnp.logical_or(it < 2, jnp.logical_and(gnorm > tol, rel_impr > tol))
        return jnp.logical_and(it < max_iter, not_converged)

    def body(carry):
        params, state, _, it = carry
        val, grad = value_and_grad(params, state=state)
        updates, state = opt.update(grad, state, params, value=val, grad=grad, value_fn=loss)
        params = optax.apply_updates(params, updates)
        return params, state, val, it + 1

    init = (params, state, jnp.asarray(jnp.inf, X.dtype), jnp.asarray(0, jnp.int32))
    params, state, _, iters = jax.lax.while_loop(cond, body, init)
    final_loss = loss(params)
    return params, final_loss, iters


# The training matrix is the big buffer (N x F f32 — 800MB at the bench
# shape) and it is dead the moment the fit returns: the donating variant
# hands X/y/mask to XLA at dispatch so their HBM is reclaimable during the
# fit instead of after Python refcounting. Used only where the platform
# consumes donations (models/pipeline.py donation_effective — CPU keeps
# donated buffers and warns, so the plain twin serves there). The old
# ``donate_argnums=()`` here donated nothing; tests/test_train_linear.py
# pins the donating twin's lowering so it can't silently regress to that.
_fit_lbfgs = partial(jax.jit, static_argnames=("max_iter",))(_fit_lbfgs_impl)
_fit_lbfgs_donating = partial(jax.jit, static_argnames=("max_iter",),
                              donate_argnums=(0, 1, 2))(_fit_lbfgs_impl)


def fit_logistic_regression(
    X,
    y,
    *,
    mesh: Optional[Mesh] = None,
    max_iter: int = 100,
    tol: float = 1e-6,
    reg_param: float = 0.0,
    threshold: float = 0.5,
    return_info: bool = False,
) -> Union[LogisticRegression, Tuple[LogisticRegression, FitInfo]]:
    """Fit binary LR on a dense (N, F) feature matrix with labels (N,) in {0,1}.

    With a mesh, rows are padded to a data-axis multiple and sharded (padded
    rows carry mask 0). Returns a ``LogisticRegression`` pytree (float32);
    with ``return_info=True`` also returns a ``FitInfo`` convergence record.
    """
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    if X.ndim != 2 or y.shape != (X.shape[0],):
        raise ValueError(f"bad shapes X{X.shape} y{y.shape}")
    mask = np.ones(X.shape[0], np.float32)
    if mesh is not None:
        Xd = mesh_lib.shard_rows(X, mesh)
        yd = mesh_lib.shard_rows(y, mesh)
        md = mesh_lib.shard_rows(mask, mesh)
    else:
        Xd, yd, md = jnp.asarray(X), jnp.asarray(y), jnp.asarray(mask)
    # Xd/yd/md are fresh uploads owned by this frame — donating them is
    # always safe; the caller's numpy arrays are untouched either way.
    from fraud_detection_tpu.models.pipeline import donation_effective

    fit = _fit_lbfgs_donating if donation_effective() else _fit_lbfgs
    (w, b), final_loss, iters = fit(
        Xd, yd, md, jnp.float32(reg_param), jnp.float32(tol), max_iter)
    model = LogisticRegression(weights=w, intercept=b, threshold=threshold)
    if return_info:
        return model, FitInfo(float(final_loss), int(iters), max_iter)
    return model
