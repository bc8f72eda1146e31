"""Centralized ICF-approximated GP regression — paper Sec. 4 baseline.

* ``icf_factor`` — pivoted incomplete Cholesky factorization of the *signal*
  kernel matrix K_DD (noise-free): returns upper-triangular-in-pivot-order
  F (R x |D|) with K_DD ~= F^T F. Never forms K_DD: only diag(K) and one
  kernel column per pivot step (O(R |D|) kernel evaluations, O(R^2 |D|) flops).
* ``icf_predict_literal`` — eqs. (28)-(29) with a dense |D|x|D| solve; the
  oracle for the Theorem 3 equivalence test.
* ``icf_predict`` — efficient centralized version via the Woodbury identity
    (F^T F + s^2 I)^{-1} = s^{-2} I - s^{-4} F^T Phi^{-1} F,
    Phi = I + s^{-2} F F^T                       (R x R),
  which is exactly what the distributed steps 3-6 compute; Table 1 row
  "ICF-based".

Zero prior mean is assumed (data pipeline centers y).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import covariance as cov
from repro.core import linalg
from repro.core.gp import GPPosterior


class ICFFactor(NamedTuple):
    F: jax.Array        # (R, n) incomplete Cholesky factor, K ~= F^T F
    pivots: jax.Array   # (R,) pivot indices in selection order
    residual: jax.Array  # (n,) remaining diagonal residual (trace error)


@linalg.f32_matmuls
def icf_factor(kfn, params, X: jax.Array, R: int) -> ICFFactor:
    """Pivoted incomplete Cholesky of the signal kernel matrix."""
    n = X.shape[0]
    d0 = cov.kdiag(kfn, params, X)                    # diag of K (signal)
    F0 = jnp.zeros((R, n), d0.dtype)
    piv0 = jnp.zeros((R,), jnp.int32)

    def step(i, carry):
        F, d, piv = carry
        p = jnp.argmax(d)
        xp = jax.lax.dynamic_slice_in_dim(X, p, 1, axis=0)       # (1, dim)
        col = kfn(params, xp, X)[0]                              # K[p, :]
        fp = F[:, p]                                             # F[:i, p] (rest 0)
        f = (col - F.T @ fp) / jnp.sqrt(jnp.maximum(d[p], 1e-30))
        F = jax.lax.dynamic_update_slice_in_dim(F, f[None], i, axis=0)
        d = jnp.maximum(d - f * f, 0.0)
        d = d.at[p].set(0.0)
        piv = piv.at[i].set(p.astype(jnp.int32))
        return F, d, piv

    F, d, piv = jax.lax.fori_loop(0, R, step, (F0, d0, piv0))
    return ICFFactor(F, piv, d)


def icf_predict_literal(kfn, params, X_train, y_train, X_test,
                        F: jax.Array) -> GPPosterior:
    """Eqs. (28)-(29) with the dense (F^T F + s^2 I) solve. Test oracle."""
    s2 = cov.noise_var(params)
    n = X_train.shape[0]
    A = F.T @ F + s2 * jnp.eye(n, dtype=F.dtype)
    A_L = linalg.chol(A, jitter=0.0)
    K_ud = kfn(params, X_test, X_train)
    mean = (K_ud @ linalg.chol_solve(A_L, y_train[:, None]))[:, 0]
    K_uu = kfn(params, X_test, X_test)
    covm = K_uu - K_ud @ linalg.chol_solve(A_L, K_ud.T)
    return GPPosterior(mean, covm)


@linalg.f32_matmuls
def icf_predict(kfn, params, X_train, y_train, X_test,
                F: jax.Array) -> GPPosterior:
    """Woodbury form — O(R^2 |D| + R |U| |D|), Table 1 row "ICF-based"."""
    s2 = cov.noise_var(params)
    R = F.shape[0]
    Phi = jnp.eye(R, dtype=F.dtype) + F @ F.T / s2            # (R, R)
    Phi_L = linalg.chol(Phi, jitter=0.0)

    K_ud = kfn(params, X_test, X_train)                       # (u, n)
    ydot = F @ y_train                                        # (R,)
    Sdot = F @ K_ud.T                                         # (R, u)
    ydd = linalg.chol_solve(Phi_L, ydot[:, None])[:, 0]       # eq. (22)
    Sdd = linalg.chol_solve(Phi_L, Sdot)                      # eq. (23)

    mean = (K_ud @ y_train) / s2 - (Sdot.T @ ydd) / s2**2     # eqs. (24),(26)
    K_uu = kfn(params, X_test, X_test)
    covm = K_uu - (K_ud @ K_ud.T) / s2 + (Sdot.T @ Sdd) / s2**2   # (25),(27)
    return GPPosterior(mean, covm)
