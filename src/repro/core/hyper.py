"""Hyperparameter learning via maximum likelihood (paper Sec. 6: MLE on a
random 10k subset; Rasmussen & Williams 2006 ch. 5).

Two objectives:
* ``gp.nlml``      — exact marginal likelihood (what the paper uses, on a
  subset small enough for O(n^3));
* ``pitc_nlml``    — the PITC approximate marginal likelihood, which is
  *distributable with the same summary trick* as prediction: per-block terms
  + one |S|x|S| all-reduce. Lets hyperparameters be fit on all data in
  parallel (beyond-paper but paper-consistent: same structural assumption).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import gp, linalg
from repro.core.ppitc import local_summary
from repro.optim.adam import Adam
from repro.parallel.runner import Runner


def pitc_nlml_machine(kfn, params, S, Xm, ym, *, axis_name) -> jax.Array:
    """-log p(y|theta) under the PITC model  N(0, Gamma_DD + Lambda).

    Uses the matrix-determinant/inversion lemmas so everything global lives in
    S-space: one psum of [quad-vector | S x S matrix | scalars]. Every machine
    returns the same (replicated) scalar. The per-block pieces are the same
    local summaries prediction caches (ppitc.local_summary) — fit and
    prediction share one summary producer.
    """
    n_m = Xm.shape[0]
    Kss = kfn(params, S, S)
    Kss_L = linalg.chol(Kss)
    local, (_, _, C_L, Wy) = local_summary(kfn, params, S, Kss_L, Xm, ym)
    quad_m = ym @ Wy                                      # y C^{-1} y
    ydot_m, Sdot_m = local.ydot, local.Sdot
    logdet_m = linalg.logdet_from_chol(C_L)
    # one fused all-reduce
    s = S.shape[0]
    packed = jnp.concatenate([
        Sdot_m, ydot_m[:, None],
        jnp.zeros((s, 1), Sdot_m.dtype).at[0, 0].set(quad_m)
            .at[1, 0].set(logdet_m)
            .at[2, 0].set(jnp.asarray(n_m, Sdot_m.dtype))], axis=1)
    packed = jax.lax.psum(packed, axis_name)
    Sdot, ydd = packed[:, :s], packed[:, s]
    quad, logdet_blocks, n = packed[0, s + 1], packed[1, s + 1], \
        packed[2, s + 1]
    # det lemma: log|Gamma+Lambda| = log|Sdd| - log|Kss| + sum_m log|C_m|
    Sdd_L = linalg.chol(Kss + Sdot)
    logdet = (linalg.logdet_from_chol(Sdd_L)
              - linalg.logdet_from_chol(Kss_L) + logdet_blocks)
    # inv lemma: y(G+L)^{-1}y = y L^{-1} y - ydd^T Sdd^{-1} ydd
    w = linalg.chol_solve(Sdd_L, ydd[:, None])[:, 0]
    quad_total = quad - ydd @ w
    return 0.5 * (quad_total + logdet + n * jnp.log(2 * jnp.pi))


def pitc_nlml(kfn, params, S, X, y, runner: Runner) -> jax.Array:
    Xb, yb = runner.shard_blocks(X), runner.shard_blocks(y)
    fn = lambda Xm, ym, params, S: pitc_nlml_machine(
        kfn, params, S, Xm, ym, axis_name=runner.axis_name)
    vals = runner.map(fn, (Xb, yb), (params, S))
    return vals[0]


def fit(kfn, params, X=None, y=None, *, steps: int = 200, lr: float = 0.05,
        objective=None) -> tuple[dict, jax.Array]:
    """Adam on the (exact, by default) negative log marginal likelihood.

    ``objective`` overrides the data-bound default entirely; (X, y) are
    only consulted — and only then required — when no objective is given,
    so custom-objective callers (fit_parallel) don't thread unused data
    through."""
    if objective is None:
        if X is None or y is None:
            raise ValueError(
                "hyper.fit needs (X, y) for the default exact-NLML "
                "objective; pass data or a custom objective")
        objective = lambda p: gp.nlml(kfn, p, X, y)
    opt = Adam(lr=lr)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(objective)(params)
        params, state = opt.update(grads, state, params)
        return params, state, loss

    losses = []
    for _ in range(steps):
        params, state, loss = step(params, state)
        losses.append(loss)
    return params, jnp.stack(losses)


def fit_parallel(kfn, params, S, X, y, runner: Runner, *, steps: int = 200,
                 lr: float = 0.05) -> tuple[dict, jax.Array]:
    """MLE on ALL data via the distributable PITC likelihood. The data is
    bound inside the objective; ``fit`` never sees it (it would only be
    captured by the unused exact-NLML default)."""
    obj = lambda p: pitc_nlml(kfn, p, S, X, y, runner)
    return fit(kfn, params, steps=steps, lr=lr, objective=obj)
