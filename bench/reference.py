"""Plain jnp reference of the served posterior, independent of the program.

pPITC (paper eqs. 3-8) and pPIC (eqs. 12-14) from raw data, whitened by
L = chol K_SS so that a float32 Cholesky holds at |D| = 32,000 and
|S| = 2,048 (Σ̈ itself has condition ~1e9 there; A = L⁻¹Σ̈L⁻ᵀ has
eigenvalues >= 1). Every matmul runs at the precision given, "highest" for
the reference; the correctness control runs the same code one step lower
("high", three bf16 passes). The regularisation is the method's own: a 1e-6
relative jitter on each Cholesky.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.linalg import cho_solve, solve_triangular

PRECISION = "highest"
CONTROL = "high"


def mm(a, b, precision):
    """a @ b at ``precision``. "high" is spelled out as the three bf16
    passes the MXU makes for it (hi·hi + hi·lo + lo·hi, each product exact
    in float32), so that the control computes the same on every
    platform."""
    if precision != CONTROL:
        return jnp.matmul(a, b, precision=precision)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    ah, bh = bf(a), bf(b)
    al, bl = bf(a - ah), bf(b - bh)
    dot = lambda x, y: jnp.matmul(x, y, precision="highest")
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def kernel(params, A, B, precision=PRECISION):
    """SE-ARD covariance k(A, B)."""
    ls = jnp.exp(params["log_lengthscale"])
    A, B = A / ls, B / ls
    d2 = (jnp.sum(A * A, 1)[:, None] + jnp.sum(B * B, 1)[None, :]
          - 2.0 * mm(A, B.T, precision))
    return jnp.exp(2.0 * params["log_signal"]) * jnp.exp(
        -0.5 * jnp.maximum(d2, 0.0))


def _chol(K):
    scale = jnp.mean(jnp.diag(K))
    return jnp.linalg.cholesky(
        K + 1e-6 * scale * jnp.eye(K.shape[0], dtype=K.dtype))


def _block(params, S, L, noise, p):
    def one(a):
        Xm, ym = a
        V = solve_triangular(L, kernel(params, S, Xm, p), lower=True)
        C_L = _chol(kernel(params, Xm, Xm, p)
                    + noise * jnp.eye(Xm.shape[0]) - mm(V.T, V, p))
        G = solve_triangular(C_L, V.T, lower=True).T          # (s, b)
        e = solve_triangular(C_L, ym, lower=True)             # C_L⁻¹ y
        Wy = solve_triangular(C_L, e, lower=True, trans=1)    # C⁻¹ y
        return V, C_L, Wy, mm(G, G.T, p), mm(G, e, p)
    return one


def summaries(params, S, blocks, precision=PRECISION):
    """Eqs. (3)-(6) whitened: per block V = L⁻¹K_SD, C_L = chol Σ_{DD|S},
    G = V C_L⁻ᵀ; globally A = I + Σ G Gᵀ and c = L⁻¹ÿ, summed over every
    (Xb, yb) of ``blocks`` (the fitted data, then any assimilated waves).
    Per-block factors are kept for the first entry (pPIC's local terms)."""
    return _summaries(params, S, blocks, precision)


@functools.partial(jax.jit, static_argnums=3)
def _summaries(params, S, blocks, precision):
    with jax.default_matmul_precision(precision):
        noise = jnp.exp(2.0 * params["log_noise"])
        L = _chol(kernel(params, S, S, precision))
        per = [jax.lax.map(_block(params, S, L, noise, precision), (Xb, yb))
               for Xb, yb in blocks]
        A = jnp.eye(S.shape[0]) + sum(jnp.sum(p[3], 0) for p in per)
        V, C_L, Wy = per[0][:3]
        return dict(L=L, A_L=jnp.linalg.cholesky(A),
                    c=sum(jnp.sum(p[4], 0) for p in per), V=V, C_L=C_L,
                    Wy=Wy)


def _global_terms(ref, W):
    return (solve_triangular(ref["A_L"], W, lower=True),
            solve_triangular(ref["A_L"], ref["c"], lower=True))


def pitc(params, S, ref, U, precision=PRECISION):
    """Eqs. (7)-(8), diagonal: (mean, var) at the rows of U."""
    return _pitc(params, S, ref, U, precision)


@functools.partial(jax.jit, static_argnums=4)
def _pitc(params, S, ref, U, precision):
    with jax.default_matmul_precision(precision):
        v = solve_triangular(ref["L"], kernel(params, S, U, precision),
                             lower=True)
        z, a = _global_terms(ref, v)
        return (mm(z.T, a, precision), jnp.exp(2.0 * params["log_signal"])
                - jnp.sum(v * v, 0) + jnp.sum(z * z, 0))


def pic(params, S, Xb, ref, U, assign, precision=PRECISION):
    """Eqs. (12)-(14), diagonal, query i answered by block ``assign[i]``.
    With R = Σ_{UD|S} = K_UD - vᵀV and P = R C⁻¹: mean = Φ Σ̈⁻¹ÿ + P y and
    var = k - |v|² - rowsum(R∘P) + |A_L⁻¹L⁻¹Φᵀ|², L⁻¹Φᵀ = v - V Pᵀ. Each
    block is evaluated on its own queries only (U padded per block)."""
    U = np.asarray(U)
    assign = np.asarray(assign)
    M = int(Xb.shape[0])
    counts = np.bincount(assign, minlength=M)
    width = max(int(counts.max()), 1)
    Ub = np.zeros((M, width, U.shape[1]), U.dtype)
    slot = np.zeros(U.shape[0], np.int64)
    for m in range(M):
        rows = np.flatnonzero(assign == m)
        Ub[m, :rows.size] = U[rows]
        slot[rows] = np.arange(rows.size)
    means, vars_ = _pic(params, S, Xb, ref, jnp.asarray(Ub), precision)
    means, vars_ = np.asarray(means), np.asarray(vars_)
    return means[assign, slot], vars_[assign, slot]


@functools.partial(jax.jit, static_argnums=5)
def _pic(params, S, Xb, ref, Ub, precision):
    sig2 = jnp.exp(2.0 * params["log_signal"])
    p = precision

    def block(a):
        Xm, V, C_L, Wy, Um = a
        v = solve_triangular(ref["L"], kernel(params, S, Um, p), lower=True)
        R = kernel(params, Um, Xm, p) - mm(v.T, V, p)           # (u, b)
        P = cho_solve((C_L, True), R.T).T                      # R C⁻¹
        z, c = _global_terms(ref, v - mm(V, P.T, p))
        return (mm(z.T, c, p) + mm(R, Wy, p),
                sig2 - jnp.sum(v * v, 0) - jnp.sum(R * P, 1)
                + jnp.sum(z * z, 0))

    with jax.default_matmul_precision(precision):
        return jax.lax.map(block, (Xb, ref["V"], ref["C_L"], ref["Wy"],
                                   Ub))


def nearest_block(U, Xb):
    """Each query's block: the nearest block mean, in float64 NumPy."""
    cent = np.asarray(Xb, np.float64).mean(axis=1)
    U = np.asarray(U, np.float64)
    return np.argmin(((U[:, None, :] - cent[None]) ** 2).sum(-1), axis=1)
