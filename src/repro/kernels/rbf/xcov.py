"""Pallas TPU kernel: fused serving hot path — cross-covariance tile +
cached triangular solves + predictive-variance quadratic form in one pass.

``predict_batch_diag`` for the summary methods (eqs. 7-8) is, per query
batch U against the cached state:

    K_US  = sig2 * exp(-0.5 ||u - s||^2)              (bq, |S|) tile
    V     = L1^{-1} K_SU,   Z = A2^{-1} V
    mean  = Z^T alpha
    var   = sig2 - ||V||^2_cols + ||Z||^2_cols

with L1 = chol K_SS, A2 the whitened Sdd factor chol(L1^{-1} Sdd L1^{-T})
and alpha the whitened weights (core/ppitc.py). FGP drops the second
factor: mean = K_US @ alpha, var = sig2 - ||V||^2_cols, L1 = chol(K + s2 I). The XLA
compose path materializes K_US in HBM and reads it back for each solve; this
kernel keeps the (bq, |S|) tile in VMEM end to end — covariance assembly on
the MXU, the cached triangular solves applied on-tile, and the
quadratic-form reduction on the VPU — so the |U| x |S| intermediate never
round-trips to HBM.

The solve realization: Mosaic has no lowering for the ``triangular_solve``
primitive, so the kernel must not call it. Instead ops.py applies the cached
solve by materializing the triangular INVERSES L^{-1} once per dispatch
(plain XLA, outside the kernel — O(|S|³) against the cached factors, dwarfed
by the O(|U||S|²) quadratic form it feeds) and the kernel computes
``V = K_US L^{-T}`` as an MXU gemm against the VMEM-resident inverse:
mathematically the cached triangular solve, realized as the matmul the MXU
can actually run. Both factors stay VMEM-resident across the whole query
grid (ops.py caps |S|_pad at 1024 to bound that residency at ~8 MiB f32).

TPU mapping:
  * grid (n/bq,): each program owns one (bq,) slice of (mean, var); the
    support set, both inverse factors, and alpha are resident;
  * accumulation dtype follows the input: f32 for f32/bf16 inputs (MXU
    accumulation), f64 for f64 — the float64 equivalence gate
    (tests/test_xcov_fused.py) runs the same kernel body in interpret mode.

Padding contract (ops.py): feature dim to a lane multiple, support rows to a
lane multiple with the inverse factors embedded in an identity (a unit
diagonal keeps padded rows inert on zeroed covariance columns), alpha
zero-padded, query rows to a block_q multiple. Padded support columns of the
covariance tile are masked to zero in-kernel against the STATIC valid count,
so they contribute nothing to mean or variance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# full f32 MXU contraction (Mosaic's fp32 contract precision); the serving
# variance is a difference of quadratic forms that one bf16 pass cannot hold
_HIGHEST = jax.lax.Precision.HIGHEST


def _xcov_diag_kernel(sig2_ref, xq_ref, xk_ref, l1inv_ref, a2inv_ref,
                      alpha_ref, mean_ref, var_ref, *, s_valid: int,
                      with_a2: bool, acc_dtype):
    xq = xq_ref[...].astype(acc_dtype)                 # (bq, d)
    xk = xk_ref[...].astype(acc_dtype)                 # (s_pad, d)
    sig2 = sig2_ref[0, 0].astype(acc_dtype)
    # MXU: cross term; VPU: norms + exp (fused RBF, see rbf.py)
    cross = jax.lax.dot_general(
        xq, xk, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=acc_dtype)              # (bq, s_pad)
    q2 = jnp.sum(xq * xq, axis=-1)[:, None]
    k2 = jnp.sum(xk * xk, axis=-1)[None, :]
    kus = sig2 * jnp.exp(-0.5 * jnp.maximum(q2 + k2 - 2.0 * cross, 0.0))
    if s_valid < kus.shape[1]:                         # static: mask padding
        cols = jax.lax.broadcasted_iota(jnp.int32, kus.shape, 1)
        kus = jnp.where(cols < s_valid, kus, 0.0)

    alpha = alpha_ref[...].astype(acc_dtype)           # (1, s_pad)
    # cached triangular solve on-tile: V = K_US L^{-T} as an MXU gemm
    # against the VMEM-resident inverse (contract over L^{-1}'s columns);
    # the variance quadratic form is then a row-wise square-reduce
    v1 = jax.lax.dot_general(
        kus, l1inv_ref[...].astype(acc_dtype), (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=acc_dtype)  # (bq, s_pad)
    var = sig2 - jnp.sum(v1 * v1, axis=1, keepdims=True)
    if with_a2:
        # the second solve chains on the first: Z = V A2^{-T}
        v2 = jax.lax.dot_general(
            v1, a2inv_ref[...].astype(acc_dtype), (((1,), (1,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=acc_dtype)
        var = var + jnp.sum(v2 * v2, axis=1, keepdims=True)
        mean = jnp.sum(v2 * alpha, axis=1, keepdims=True)
    else:
        mean = jnp.sum(kus * alpha, axis=1, keepdims=True)  # row-reduce
    mean_ref[...] = mean.astype(mean_ref.dtype)
    var_ref[...] = var.astype(var_ref.dtype)


@functools.partial(jax.jit, static_argnames=("s_valid", "with_a2", "block_q",
                                             "interpret"))
def xcov_diag_pallas(Xq: jax.Array, Xk: jax.Array, L1inv: jax.Array,
                     A2inv: jax.Array, alpha: jax.Array, sig2: jax.Array, *,
                     s_valid: int, with_a2: bool = True, block_q: int = 128,
                     interpret: bool = False):
    """Tiled fused serving kernel. Caller guarantees n % block_q == 0 and
    Xk/L1inv/A2inv/alpha padded per the module contract — L1inv/A2inv are
    the lower-triangular INVERSE factors (ops.py does all of this).
    Returns ((n,) mean, (n,) var) in Xq's dtype."""
    n, d = Xq.shape
    s_pad = Xk.shape[0]
    acc_dtype = jnp.float64 if Xq.dtype == jnp.float64 else jnp.float32
    sig2 = jnp.asarray(sig2, acc_dtype).reshape(1, 1)
    grid = (n // block_q,)
    kernel = functools.partial(_xcov_diag_kernel, s_valid=s_valid,
                               with_a2=with_a2, acc_dtype=acc_dtype)
    mean, var = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),            # sig2
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),      # Xq tile
            pl.BlockSpec((s_pad, d), lambda i: (0, 0)),        # support set
            pl.BlockSpec((s_pad, s_pad), lambda i: (0, 0)),    # L1^{-1}
            pl.BlockSpec((s_pad, s_pad), lambda i: (0, 0)),    # A2^{-1}
            pl.BlockSpec((1, s_pad), lambda i: (0, 0)),        # alpha
        ],
        # (n, 1) column outputs: a (block_q, 1) block meets the TPU tiling
        # rule (block_q % 8 == 0, last dim equal to the array's) for every
        # serving tile from 8 up; a (1, block_q) row block would need
        # block_q % 128 == 0
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), Xq.dtype),
            jax.ShapeDtypeStruct((n, 1), Xq.dtype),
        ],
        interpret=interpret,
    )(sig2, Xq, Xk, L1inv, A2inv, alpha)
    return mean[:, 0], var[:, 0]
