"""jit'd public wrappers for the fused RBF kernels (covariance + serving).

Handles padding (rows to block multiples, feature dim to a 128 multiple for
MXU alignment), VMEM-aware block-size selection, and the CPU fallback
(interpret mode executes the kernel body in Python — correct but slow, so the
wrappers only route through Pallas when asked or when on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rbf import ref
from repro.kernels.rbf.rbf import rbf_pallas
from repro.kernels.rbf.xcov import xcov_diag_pallas

_LANE = 128
_VMEM_BUDGET = 8 * 1024 * 1024   # bytes, conservative half of v5e VMEM
# largest support-set padding the fused serving kernel keeps VMEM-resident:
# two (s_pad, s_pad) f32 Cholesky factors at 1024 are 8 MiB total
MAX_FUSED_RESIDENT = 1024


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pick_blocks(n: int, m: int, d_padded: int,
                itemsize: int = 4) -> tuple[int, int]:
    """Largest hardware-aligned (block_q, block_k) whose tile working set
    (two input tiles + f32 output tile) fits the VMEM budget."""
    for b in (512, 256, 128):
        bq, bk = min(b, n), min(b, m)
        bytes_needed = (bq + bk) * d_padded * itemsize + bq * bk * 4
        if bytes_needed <= _VMEM_BUDGET:
            return max(bq, 8), max(bk, _LANE)
    return 8, _LANE


@functools.partial(jax.jit, static_argnames=("impl",))
def rbf_covariance(Xq: jax.Array, Xk: jax.Array, sig2, *,
                   impl: str = "auto") -> jax.Array:
    """sig2 * exp(-0.5 ||x-z||^2) over pre-scaled inputs; (n,d),(m,d)->(n,m).

    impl: "auto" (pallas on TPU, jnp elsewhere), "pallas" (compiled),
          "pallas_interpret" (Python-executed kernel body — for validation),
          "jnp" (reference).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "jnp":
        return ref.rbf_covariance(Xq, Xk, sig2)

    n, d = Xq.shape
    m = Xk.shape[0]
    Xq_p = _pad_to(Xq, 1, _LANE)
    Xk_p = _pad_to(Xk, 1, _LANE)
    bq, bk = pick_blocks(n, m, Xq_p.shape[1], Xq.dtype.itemsize)
    Xq_p = _pad_to(Xq_p, 0, bq)
    Xk_p = _pad_to(Xk_p, 0, bk)
    out = rbf_pallas(Xq_p, Xk_p, sig2, block_q=bq, block_k=bk,
                     interpret=(impl == "pallas_interpret"))
    return out[:n, :m]


def pick_serve_block_q(n: int) -> int:
    """Query-tile size for the fused serving kernel at batch size n: the
    largest sublane-aligned power of two not exceeding the (8-aligned) batch,
    so small microbatches pad by < 2x and large ones tile at 256. This is
    what ``launch.gp_serve.default_buckets`` aligns its bucket ladder to
    (serving-shape selection benchmarked in benchmarks/bench_kernels.py)."""
    for b in (256, 128, 64, 32, 16):
        if n >= b:
            return b
    return 8


def _embed_tri_inv(L: jax.Array, s_pad: int) -> jax.Array:
    """(s, s) Cholesky factor -> (s_pad, s_pad) lower-triangular INVERSE,
    embedded in an identity. Materializing L^{-1} here (plain XLA, outside
    the kernel) is what lets the kernel apply the cached solve as an MXU
    gemm — Mosaic cannot lower the triangular_solve primitive in-kernel.
    The unit diagonal of the padding block keeps padded rows inert on the
    masked-to-zero covariance columns."""
    s = L.shape[0]
    Linv = jax.lax.linalg.triangular_solve(
        L, jnp.eye(s, dtype=L.dtype), left_side=True, lower=True)
    if s == s_pad:
        return Linv
    return jnp.eye(s_pad, dtype=L.dtype).at[:s, :s].set(Linv)


@functools.partial(jax.jit, static_argnames=("impl", "block_q"))
def xcov_diag(Xq: jax.Array, Xk: jax.Array, L1: jax.Array, alpha: jax.Array,
              sig2, A2: jax.Array | None = None, *, impl: str = "auto",
              block_q: int | None = None):
    """Fused serving hot path over pre-scaled inputs: (mean, var) of the
    summary-method diag predict (see kernels/rbf/xcov.py) without the
    (n, |S|) HBM round-trip.

    Xq: (n, d) queries, Xk: (s, d) support/training set, L1/A2: (s, s)
    cached lower Cholesky factors, alpha: (s,) cached weights. Without
    ``A2``: mean = K_US alpha, var = sig2 - |L1⁻¹K_SU|². With it:
    Z = A2⁻¹ L1⁻¹ K_SU, mean = Zᵀ alpha, var = sig2 - |L1⁻¹K_SU|² + |Z|²
    (the whitened pPITC state). impl as in ``rbf_covariance``.
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "jnp":
        return ref.xcov_diag(Xq, Xk, L1, alpha, sig2, A2)

    n, _ = Xq.shape
    s = Xk.shape[0]
    Xq_p = _pad_to(Xq, 1, _LANE)
    Xk_p = _pad_to(Xk, 1, _LANE)
    s_pad = -(-s // _LANE) * _LANE
    if s_pad > MAX_FUSED_RESIDENT:
        raise ValueError(
            f"|S|={s} exceeds the fused kernel's VMEM residency cap "
            f"{MAX_FUSED_RESIDENT}; use the compose path (impl='jnp')")
    Xk_p = _pad_to(Xk_p, 0, s_pad)
    with_a2 = A2 is not None
    L1_p = _embed_tri_inv(L1, s_pad)
    A2_p = _embed_tri_inv(A2, s_pad) if with_a2 else L1_p
    alpha_p = _pad_to(alpha[None, :], 1, s_pad)
    bq = block_q or pick_serve_block_q(n)
    Xq_p = _pad_to(Xq_p, 0, bq)
    mean, var = xcov_diag_pallas(Xq_p, Xk_p, L1_p, A2_p, alpha_p, sig2,
                                 s_valid=s, with_a2=with_a2, block_q=bq,
                                 interpret=(impl == "pallas_interpret"))
    return mean[:n], var[:n]
