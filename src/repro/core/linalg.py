"""Shared PSD linear-algebra helpers used across the GP stack.

All solves in this package funnel through these helpers so that jitter policy
and dtype behaviour are uniform (the paper's MPI/LAPACK float64 pipeline maps
onto jax.scipy cholesky solves; equivalence tests run in float64, performance
paths in float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Every f32 matmul of the GP pipeline runs at full f32 precision. On TPU
# an f32 dot that names no precision is a single bf16 pass: the kernel
# matrices lose positive-definiteness and the Cholesky pipeline (and the
# served mean/variance) turn non-finite at the paper's |S| = 2048.
MATMUL_PRECISION = "highest"


def f32_matmuls(fn):
    """Decorator: run ``fn`` with every matmul that names no precision of
    its own at ``MATMUL_PRECISION``. The precision is recorded when ``fn``
    is traced, so a jitted caller's compiled program keeps it. Applied to
    the per-machine programs (``Runner.map``), the serving executables
    (``ServePlan``), the S-space assembly between them and the public fit
    and predict functions of the GP methods."""
    @functools.wraps(fn)
    def pinned(*args, **kwargs):
        with jax.default_matmul_precision(MATMUL_PRECISION):
            return fn(*args, **kwargs)
    return pinned


# Jitter scaled to dtype: float64 paths need far less regularisation.
_JITTER = {jnp.float64.dtype: 1e-10, jnp.float32.dtype: 1e-6}


def default_jitter(dtype) -> float:
    return _JITTER.get(jnp.dtype(dtype), 1e-6)


def add_jitter(K: jax.Array, jitter: float | None = None) -> jax.Array:
    """K + jitter * mean(diag(K)) * I — relative jitter keeps scale-invariance."""
    if jitter is None:
        jitter = default_jitter(K.dtype)
    scale = jnp.mean(jnp.diag(K))
    return K + (jitter * scale) * jnp.eye(K.shape[-1], dtype=K.dtype)


def chol(K: jax.Array, jitter: float | None = None) -> jax.Array:
    """Lower Cholesky factor of a PSD matrix with relative jitter."""
    return jnp.linalg.cholesky(add_jitter(K, jitter))


def chol_solve(L: jax.Array, B: jax.Array) -> jax.Array:
    """Solve (L Lᵀ) X = B given lower Cholesky L."""
    return jax.scipy.linalg.cho_solve((L, True), B)


def chol_solve_right(L: jax.Array, A: jax.Array) -> jax.Array:
    """Solve X (L Lᵀ) = A given lower Cholesky L — i.e. A (L Lᵀ)⁻¹ with A's
    ROWS as the batch axis (= ``chol_solve(L, A.T).T`` mathematically).

    Exists for bitwise row-stability, not speed: serving paths that must be
    batch-composition-invariant bit-for-bit (ppic routed prediction) keep
    the query axis on matrix rows everywhere, because XLA's *batched*
    left-sided triangular solve (and gemms with queries on the column axis)
    pick panel strategies that make a column's float path depend on its
    position and on the total width — row-sided solves and row-major gemms
    do not (tests/test_routing_equivalence.py)."""
    t = jax.lax.linalg.triangular_solve(L, A, left_side=False, lower=True,
                                        transpose_a=True)    # X Lᵀ = A
    return jax.lax.linalg.triangular_solve(L, t, left_side=False, lower=True,
                                           transpose_a=False)  # X L = t


def psd_solve(K: jax.Array, B: jax.Array, jitter: float | None = None) -> jax.Array:
    """Solve K X = B for PSD K via jittered Cholesky."""
    return chol_solve(chol(K, jitter), B)


def psd_inv(K: jax.Array, jitter: float | None = None) -> jax.Array:
    return psd_solve(K, jnp.eye(K.shape[-1], dtype=K.dtype), jitter)


def tri_solve(L: jax.Array, B: jax.Array, *, lower: bool = True,
              trans: bool = False) -> jax.Array:
    return jax.scipy.linalg.solve_triangular(L, B, lower=lower,
                                             trans=1 if trans else 0)


def logdet_from_chol(L: jax.Array) -> jax.Array:
    return 2.0 * jnp.sum(jnp.log(jnp.diag(L)))


# ---------------------------------------------------------------------------
# Rank-1 / rank-b Cholesky updates (paper Sec. 5.2 incremental summaries).
#
# The streaming argument needs chol(A + W Wᵀ) from chol(A) without the O(n³)
# refactorization: one LINPACK-style rotation sweep per update vector is
# O(n²), so folding a b-column factor costs O(n² b). ``sign=-1`` is the
# downdate (machine retirement / summary subtraction); it is well-defined
# only while A - W Wᵀ stays positive definite — exactly the summary-algebra
# guarantee (removing a block's PSD contribution from Sdd never crosses
# K_SS), so no rank-revealing fallback is needed here.
# ---------------------------------------------------------------------------

def _chol_rank1(L: jax.Array, w: jax.Array, sign: float) -> jax.Array:
    """chol(L Lᵀ + sign·w wᵀ) via one sweep of (hyperbolic) rotations."""
    n = L.shape[0]
    idx = jnp.arange(n)

    def body(k, carry):
        L, w = carry
        Lkk, wk = L[k, k], w[k]
        r = jnp.sqrt(jnp.maximum(Lkk * Lkk + sign * wk * wk,
                                 jnp.finfo(L.dtype).tiny))
        c, s = r / Lkk, wk / Lkk
        below = idx > k
        col = jnp.where(below, (L[:, k] + sign * s * w) / c, L[:, k])
        col = col.at[k].set(r)
        w = jnp.where(below, c * w - s * col, w)
        return L.at[:, k].set(col), w

    L, _ = jax.lax.fori_loop(0, n, body, (L, w))
    return L


@jax.jit
def cholupdate(L: jax.Array, w: jax.Array) -> jax.Array:
    """Lower Cholesky of (L Lᵀ + w wᵀ) in O(n²)."""
    return _chol_rank1(L, w, 1.0)


@jax.jit
def choldowndate(L: jax.Array, w: jax.Array) -> jax.Array:
    """Lower Cholesky of (L Lᵀ - w wᵀ) in O(n²); requires the difference to
    remain positive definite (guaranteed when removing a PSD contribution
    that was previously folded in)."""
    return _chol_rank1(L, w, -1.0)


@functools.partial(jax.jit, static_argnames=("sign",))
def chol_update_rank(L: jax.Array, W: jax.Array, *,
                     sign: float = 1.0) -> jax.Array:
    """Lower Cholesky of (L Lᵀ + sign·W Wᵀ) for an (n, b) factor W: b
    sequential rank-1 sweeps, O(n² b) total — the incremental ``to_state``
    path (vs O(n³) refactorization). Jitted (one executable per (n, b)
    shape): the sweeps are sequential scalar-ish work that would otherwise
    pay per-op dispatch on the streaming hot path."""
    def step(L, w):
        return _chol_rank1(L, w, sign), None

    L, _ = jax.lax.scan(step, L, W.T)
    return L
