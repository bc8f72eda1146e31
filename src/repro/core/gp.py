"""Full (exact) Gaussian process regression — paper Sec. 2, eqs. (1)-(2).

This is FGP: the O(|D|^3) centralized baseline every approximation is measured
against (paper Figs. 1-3). Split into ``fit`` (the O(|D|^3) Cholesky, cached
in an ``api.FGPState``) and ``predict_batch`` (O(|U||D|) per query batch);
``predict`` remains as the one-shot wrapper over the two.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import api
from repro.core import covariance as cov
from repro.core import linalg


class GPPosterior(NamedTuple):
    """Predictive Gaussian N(mean, cov); ``var`` is diag(cov)."""
    mean: jax.Array
    cov: jax.Array

    @property
    def var(self) -> jax.Array:
        return jnp.diag(self.cov)


@linalg.f32_matmuls
def fit(kfn: cov.KernelFn, params: dict, X_train: jax.Array,
        y_train: jax.Array, **_) -> api.FGPState:
    """Cache chol(K_DD + noise) and its solve against y (zero prior mean)."""
    K_dd = cov.add_noise(kfn(params, X_train, X_train), params)
    L = linalg.chol(K_dd)
    alpha = linalg.chol_solve(L, y_train[:, None])[:, 0]
    return api.FGPState(X_train, L, alpha)


@linalg.f32_matmuls
def predict_batch(kfn: cov.KernelFn, params: dict, state: api.FGPState,
                  X_test: jax.Array, *, diag_only: bool = False) -> GPPosterior:
    """Eqs. (1)-(2) from the cached factors: no |D|^3 work per query."""
    K_ud = kfn(params, X_test, state.X)
    mean = K_ud @ state.alpha
    V = linalg.tri_solve(state.L, K_ud.T)     # L^{-1} K_du
    if diag_only:
        var = cov.kdiag(kfn, params, X_test) - jnp.sum(V * V, axis=0)
        return GPPosterior(mean, jnp.diag(var))
    K_uu = kfn(params, X_test, X_test)
    return GPPosterior(mean, K_uu - V.T @ V)


@linalg.f32_matmuls
def predict_batch_diag(kfn, params, state: api.FGPState, X_test):
    """(mean, var) vectors — no |U|x|U| intermediates (serving hot path).

    With a Pallas ``cov.KernelSpec`` and a VMEM-resident training factor
    (|D| within the fused residency cap) this is one ``xcov_diag`` dispatch:
    FGP is the L2-less case of the fused serving kernel (var = sig2 - q(L))."""
    if isinstance(kfn, cov.KernelSpec) and kfn.fuse(state.X.shape[0]):
        return kfn.fused_diag(params, X_test, state.X, state.L, state.alpha)
    K_ud = kfn(params, X_test, state.X)
    mean = K_ud @ state.alpha
    V = linalg.tri_solve(state.L, K_ud.T)
    var = cov.kdiag(kfn, params, X_test) - jnp.sum(V * V, axis=0)
    return mean, var


@linalg.f32_matmuls
def predict(kfn: cov.KernelFn, params: dict,
            X_train: jax.Array, y_train: jax.Array, X_test: jax.Array,
            mean_fn=None, *, diag_only: bool = False) -> GPPosterior:
    """One-shot eqs. (1)-(2): thin wrapper over fit + predict_batch."""
    if mean_fn is None:
        state = fit(kfn, params, X_train, y_train)
        return predict_batch(kfn, params, state, X_test, diag_only=diag_only)

    # non-zero prior mean: legacy inline path (mean_fn is not state-cacheable)
    mu_d = _mean(mean_fn, X_train, y_train.dtype)
    mu_u = _mean(mean_fn, X_test, y_train.dtype)
    K_dd = cov.add_noise(kfn(params, X_train, X_train), params)
    K_ud = kfn(params, X_test, X_train)
    L = linalg.chol(K_dd)
    alpha = linalg.chol_solve(L, (y_train - mu_d)[:, None])[:, 0]
    mean = mu_u + K_ud @ alpha
    V = linalg.tri_solve(L, K_ud.T)           # L^{-1} K_du
    if diag_only:
        var = cov.kdiag(kfn, params, X_test) - jnp.sum(V * V, axis=0)
        return GPPosterior(mean, jnp.diag(var))
    K_uu = kfn(params, X_test, X_test)
    return GPPosterior(mean, K_uu - V.T @ V)


@linalg.f32_matmuls
def nlml(kfn: cov.KernelFn, params: dict,
         X_train: jax.Array, y_train: jax.Array, mean_fn=None) -> jax.Array:
    """Negative log marginal likelihood -log p(y_D | theta) for MLE."""
    n = X_train.shape[0]
    mu_d = _mean(mean_fn, X_train, y_train.dtype)
    K = cov.add_noise(kfn(params, X_train, X_train), params)
    L = linalg.chol(K)
    r = (y_train - mu_d)[:, None]
    alpha = linalg.chol_solve(L, r)
    return 0.5 * (r.T @ alpha)[0, 0] + 0.5 * linalg.logdet_from_chol(L) \
        + 0.5 * n * jnp.log(2.0 * jnp.pi)


def _mean(mean_fn, X: jax.Array, dtype) -> jax.Array:
    if mean_fn is None:
        return jnp.zeros((X.shape[0],), dtype)
    return mean_fn(X)


api.register(api.GPMethod("fgp", fit, predict_fn=predict_batch,
                          predict_diag_fn=predict_batch_diag))
