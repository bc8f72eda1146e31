"""Pure-jnp oracles for the fused RBF kernels (covariance + serving diag)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rbf_covariance(Xq: jax.Array, Xk: jax.Array, sig2) -> jax.Array:
    """sig2 * exp(-0.5 ||x - z||^2) for pre-lengthscale-scaled inputs.

    Xq: (n, d), Xk: (m, d) -> (n, m). Accumulates in float32 regardless of
    input dtype (matches the kernel's MXU accumulation).
    """
    Xq32 = Xq.astype(jnp.float32)
    Xk32 = Xk.astype(jnp.float32)
    q2 = jnp.sum(Xq32 * Xq32, axis=-1)[:, None]
    k2 = jnp.sum(Xk32 * Xk32, axis=-1)[None, :]
    cross = Xq32 @ Xk32.T
    d2 = jnp.maximum(q2 + k2 - 2.0 * cross, 0.0)
    out = jnp.asarray(sig2, jnp.float32) * jnp.exp(-0.5 * d2)
    return out.astype(Xq.dtype)


def xcov_diag(Xq: jax.Array, Xk: jax.Array, L1: jax.Array, alpha: jax.Array,
              sig2, A2: jax.Array | None = None):
    """Compose-path oracle for the fused serving kernel (xcov.py).

    Builds K_US dense, applies the cached triangular solves, reduces the
    variance quadratic form — the exact math ``ppitc.predict_batch_diag``
    (A2 = the whitened Sdd factor, alpha the whitened weights) and
    ``gp.predict_batch_diag`` (A2 = None) perform, over
    pre-lengthscale-scaled inputs. Accumulates in f32 for <=f32 inputs and
    f64 for f64, matching the kernel's accumulation dtype.
    """
    acc = jnp.float64 if Xq.dtype == jnp.float64 else jnp.float32
    Xqa, Xka = Xq.astype(acc), Xk.astype(acc)
    q2 = jnp.sum(Xqa * Xqa, axis=-1)[:, None]
    k2 = jnp.sum(Xka * Xka, axis=-1)[None, :]
    d2 = jnp.maximum(q2 + k2 - 2.0 * (Xqa @ Xka.T), 0.0)
    sig2 = jnp.asarray(sig2, acc)
    kus = sig2 * jnp.exp(-0.5 * d2)                    # (n, s)
    v1 = jax.lax.linalg.triangular_solve(
        L1.astype(acc), kus, left_side=False, lower=True, transpose_a=True)
    var = sig2 - jnp.sum(v1 * v1, axis=1)
    if A2 is None:
        mean = jnp.sum(kus * alpha.astype(acc)[None, :], axis=1)
    else:
        v2 = jax.lax.linalg.triangular_solve(
            A2.astype(acc), v1, left_side=False, lower=True,
            transpose_a=True)
        var = var + jnp.sum(v2 * v2, axis=1)
        mean = jnp.sum(v2 * alpha.astype(acc)[None, :], axis=1)
    return mean.astype(Xq.dtype), var.astype(Xq.dtype)
