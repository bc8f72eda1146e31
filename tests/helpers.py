"""Test helpers: canonical small GP problem generators."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import covariance as cov


def make_problem(*, n=96, u=24, s=12, d=3, M=4, noise=0.3, lengthscale=1.5,
                 seed=0, dtype=jnp.float64):
    """Random smooth regression problem sized for M machines."""
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = jax.random.normal(k0, (n, d), dtype)
    S = jax.random.normal(k1, (s, d), dtype)
    U = jax.random.normal(k2, (u, d), dtype)
    params = cov.init_params(d, signal=1.3, noise=noise,
                             lengthscale=lengthscale, dtype=dtype)
    f = lambda Z: jnp.sin(Z[:, 0]) * 2.0 + Z[:, 1] - 0.5 * Z[:, 2] ** 2
    y = f(X) + noise * jax.random.normal(k3, (n,), dtype)
    return dict(X=X, y=y, S=S, U=U, f=f, params=params,
                kfn=cov.make_kernel("se"), M=M)


def block_diag_err(full_cov, blocks):
    """max |diag-block difference| between a dense cov and stacked blocks."""
    M, b, _ = blocks.shape
    errs = []
    for m in range(M):
        sl = slice(m * b, (m + 1) * b)
        errs.append(jnp.abs(full_cov[sl, sl] - blocks[m]).max())
    return float(jnp.stack(errs).max())
