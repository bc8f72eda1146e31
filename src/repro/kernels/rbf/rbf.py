"""Pallas TPU kernel: fused pairwise-squared-distance + exp (RBF covariance).

This is the dominant FLOP producer of the paper's local-summary construction
(K_SD_m, diagonal blocks of K_D_mD_m, K_UD_m): a GEMM-shaped cross term plus
elementwise exp, fused so the (n x m) distance matrix never round-trips to
HBM.

TPU mapping:
  * grid (n/bq, m/bk); each program owns a (bq, bk) output tile in VMEM.
  * inputs arrive as (bq, d) / (bk, d) VMEM tiles — ops.py pads d to a
    multiple of 128 so the cross term runs on the MXU with aligned tiles
    (zero-padding feature dims does not change distances).
  * cross = Xq @ Xk^T on the MXU (f32 accumulation), norms + exp on the VPU.
  * arithmetic intensity ~ d/2 FLOPs per output byte for the GEMM part plus
    the transcendental; with bq=bk=256 the tile working set is
    (bq+bk)*d + bq*bk floats — ops.py picks block sizes to stay under ~8 MiB
    of VMEM.

Validated against ref.py in interpret mode (tests/test_kernels.py sweeps
shapes and dtypes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# full f32 MXU contraction (Mosaic's fp32 contract precision): the distance
# ||x||^2 + ||z||^2 - 2 x.z cancels, and one bf16 pass leaves K_SS indefinite
_HIGHEST = jax.lax.Precision.HIGHEST


def _rbf_kernel(sig2_ref, xq_ref, xk_ref, out_ref):
    xq = xq_ref[...].astype(jnp.float32)          # (bq, d)
    xk = xk_ref[...].astype(jnp.float32)          # (bk, d)
    # MXU: cross terms; VPU: norms + exp
    cross = jax.lax.dot_general(
        xq, xk, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)       # (bq, bk)
    q2 = jnp.sum(xq * xq, axis=-1)[:, None]
    k2 = jnp.sum(xk * xk, axis=-1)[None, :]
    d2 = jnp.maximum(q2 + k2 - 2.0 * cross, 0.0)
    out_ref[...] = (sig2_ref[0, 0] * jnp.exp(-0.5 * d2)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def rbf_pallas(Xq: jax.Array, Xk: jax.Array, sig2: jax.Array, *,
               block_q: int = 256, block_k: int = 256,
               interpret: bool = False) -> jax.Array:
    """Tiled fused RBF covariance. Caller guarantees n % block_q == 0,
    m % block_k == 0 (ops.py pads)."""
    n, d = Xq.shape
    m, _ = Xk.shape
    grid = (n // block_q, m // block_k)
    sig2 = jnp.asarray(sig2, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _rbf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),          # sig2
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),    # Xq tile
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),    # Xk tile
        ],
        out_specs=pl.BlockSpec((block_q, block_k), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), Xq.dtype),
        interpret=interpret,
    )(sig2, Xq, Xk)
