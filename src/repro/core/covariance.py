"""Covariance (kernel) functions for GP regression.

A kernel is a pure function ``k(params, X1, X2) -> (n1, n2)`` over the *signal*
part only; observation noise sigma_n^2 * I is added explicitly where the paper's
equations call for it (the paper's sigma_xx' includes a Kronecker-delta noise
term — we keep it separate so that cross-covariances K_SD, K_UD never
accidentally carry noise).

Params are stored in log-space for unconstrained MLE (core/hyper.py):
  {"log_signal": (), "log_noise": (), "log_lengthscale": (d,)}

The squared-exponential path can route through the Pallas TPU kernel
(kernels/rbf) when ``impl="pallas"`` — the fused pairwise-distance+exp tiling is
the dominant FLOP producer of local-summary construction.

``KernelSpec`` is the serving-side kernel abstraction: a callable drop-in for
any bare ``KernelFn`` that additionally DECLARES how cross-covariances should
be built (dense jnp vs the fused Pallas tiling) and whether the predict paths
may collapse covariance + cached solves + variance reduction into the fused
``xcov_diag`` serving kernel (kernels/rbf/xcov.py). Every registered predict
path accepts a spec wherever it accepts a kernel function — the spec routes
``k(params, X1, X2)`` through its declared implementation transparently, so
``ppic``/``picf``/``fgp`` cross-covariance assembly moves onto the Pallas hot
path without touching their math.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

KernelFn = Callable[[dict, jax.Array, jax.Array], jax.Array]


def init_params(d: int, *, signal: float = 1.0, noise: float = 0.1,
                lengthscale: float | jax.Array = 1.0,
                dtype=jnp.float32) -> dict:
    ls = jnp.broadcast_to(jnp.asarray(lengthscale, dtype), (d,))
    return {
        "log_signal": jnp.asarray(math.log(signal), dtype),
        "log_noise": jnp.asarray(math.log(noise), dtype),
        "log_lengthscale": jnp.log(ls),
    }


def signal_var(params: dict) -> jax.Array:
    return jnp.exp(2.0 * params["log_signal"])


def noise_var(params: dict) -> jax.Array:
    return jnp.exp(2.0 * params["log_noise"])


def _scale(params: dict, X: jax.Array) -> jax.Array:
    return X / jnp.exp(params["log_lengthscale"])


def _sqdist(A: jax.Array, B: jax.Array) -> jax.Array:
    """Pairwise squared distances, clamped at 0 against roundoff."""
    a2 = jnp.sum(A * A, axis=-1)[:, None]
    b2 = jnp.sum(B * B, axis=-1)[None, :]
    d2 = a2 + b2 - 2.0 * (A @ B.T)
    return jnp.maximum(d2, 0.0)


def se_ard(params: dict, X1: jax.Array, X2: jax.Array) -> jax.Array:
    """Squared-exponential ARD kernel (paper Sec. 6, signal part)."""
    d2 = _sqdist(_scale(params, X1), _scale(params, X2))
    return signal_var(params) * jnp.exp(-0.5 * d2)


def se_ard_pallas(params: dict, X1: jax.Array, X2: jax.Array) -> jax.Array:
    """SE-ARD routed through the Pallas fused kernel (TPU hot path)."""
    from repro.kernels.rbf import ops as rbf_ops
    return rbf_ops.rbf_covariance(
        _scale(params, X1), _scale(params, X2), signal_var(params))


def matern52(params: dict, X1: jax.Array, X2: jax.Array) -> jax.Array:
    d2 = _sqdist(_scale(params, X1), _scale(params, X2))
    r = jnp.sqrt(d2 + 1e-12) * math.sqrt(5.0)
    return signal_var(params) * (1.0 + r + r * r / 3.0) * jnp.exp(-r)


def rational_quadratic(params: dict, X1: jax.Array, X2: jax.Array,
                       alpha: float = 1.0) -> jax.Array:
    d2 = _sqdist(_scale(params, X1), _scale(params, X2))
    return signal_var(params) * (1.0 + d2 / (2.0 * alpha)) ** (-alpha)


KERNELS: dict[str, KernelFn] = {
    "se": se_ard,
    "se_pallas": se_ard_pallas,
    "matern52": matern52,
    "rq": partial(rational_quadratic, alpha=1.0),
}


def make_kernel(name: str) -> KernelFn:
    try:
        return KERNELS[name]
    except KeyError:
        raise ValueError(f"unknown kernel {name!r}; have {sorted(KERNELS)}")


# ---------------------------------------------------------------------------
# KernelSpec — the serving-side kernel abstraction (hot-path declaration).
# ---------------------------------------------------------------------------

_SE_FAMILY = ("se", "se_pallas")


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A kernel plus its declared cross-covariance/serving implementation.

    Callable with the ``KernelFn`` signature, so it drops into every fit and
    predict path unchanged. What it adds over a bare function:

    * ``impl`` — how cross-covariances are assembled: ``"auto"`` (Pallas on
      TPU, dense jnp elsewhere), ``"pallas"`` (compiled kernel),
      ``"pallas_interpret"`` (Python-executed kernel body, for validation on
      CPU), ``"jnp"`` (always dense). Only the SE family has a Pallas
      realization; other kernels fall through to their dense fn.
    * ``fused`` — allow predict paths with S-space cached factors (ppitc /
      pitc eqs. 7-8, fgp eqs. 1-2) to dispatch the fused ``xcov_diag``
      serving kernel: covariance tile + cached triangular solve + variance
      quadratic form in one VMEM-resident pass (kernels/rbf/xcov.py).
      Honoured only when ``impl`` resolves to a Pallas mode and the cached
      factor fits the kernel's VMEM residency cap.
    * ``block_q`` — serving tile override; also consumed by the two-bucket
      routed scatter (ppic.predict_routed_diag) and ``default_buckets`` so
      microbatch padding lands on kernel tile boundaries.

    Frozen/hashable: safe to close over in jitted serving functions.
    """
    name: str = "se"
    impl: str = "auto"
    fused: bool = True
    block_q: int | None = None

    @property
    def kfn(self) -> KernelFn:
        return make_kernel(self.name)

    def resolved_impl(self) -> str:
        if self.impl == "auto":
            return "pallas" if jax.default_backend() == "tpu" else "jnp"
        return self.impl

    def __call__(self, params: dict, X1: jax.Array, X2: jax.Array):
        impl = self.resolved_impl()
        if self.name not in _SE_FAMILY or impl == "jnp":
            # dense path in the native dtype (float64 equivalence tests)
            return (se_ard if self.name in _SE_FAMILY else self.kfn)(
                params, X1, X2)
        from repro.kernels.rbf import ops as rbf_ops
        return rbf_ops.rbf_covariance(
            _scale(params, X1), _scale(params, X2), signal_var(params),
            impl=impl)

    def diag(self, params: dict, X: jax.Array) -> jax.Array:
        """diag k(X, X) — constant sig2 for the stationary kernels this
        registry carries (no per-row kernel dispatch)."""
        return jnp.full((X.shape[0],), signal_var(params), X.dtype)

    def fuse(self, k: int) -> bool:
        """May the S-space diag predict collapse into ``xcov_diag`` for a
        cached factor of size k? (Pallas impl + VMEM-resident factor.)"""
        from repro.kernels.rbf import ops as rbf_ops
        return (self.fused and self.name in _SE_FAMILY
                and self.resolved_impl() in ("pallas", "pallas_interpret")
                and -(-k // 128) * 128 <= rbf_ops.MAX_FUSED_RESIDENT)

    def fused_diag(self, params: dict, U: jax.Array, Xk: jax.Array,
                   L1: jax.Array, alpha: jax.Array,
                   A2: jax.Array | None = None):
        """Dispatch the fused serving kernel over lengthscale-scaled inputs
        (``rbf_ops.xcov_diag``; ``A2`` is the whitened second factor)."""
        from repro.kernels.rbf import ops as rbf_ops
        return rbf_ops.xcov_diag(
            _scale(params, U), _scale(params, Xk), L1, alpha,
            signal_var(params), A2, impl=self.resolved_impl(),
            block_q=self.block_q)


_IMPLS = ("auto", "pallas", "pallas_interpret", "jnp")


def xla_kernel(kfn):
    """``kfn`` with its cross-covariances built by XLA instead of the
    compiled Pallas kernel, or ``kfn`` itself when it runs no such kernel.

    XLA partitions a program whose arrays span several devices; a Mosaic
    (Pallas TPU) call it cannot partition outside ``shard_map``, so a
    serving plan over such a state takes this kernel
    (``api.GPMethod.plan``)."""
    if kfn is se_ard_pallas:
        return se_ard
    if isinstance(kfn, KernelSpec) and kfn.name in _SE_FAMILY \
            and kfn.resolved_impl() == "pallas":
        return dataclasses.replace(kfn, impl="jnp")
    return kfn


def make_spec(name: str = "se", *, impl: str = "auto", fused: bool = True,
              block_q: int | None = None) -> KernelSpec:
    """Front door for the serving kernel-spec knob (README "Performance").

    Validates eagerly: the spec's declared ``block_q`` becomes the serving
    tile that bucket ladders and the routed scatter align to
    (``api.ServeSpec.resolve_block_q``), so a non-positive tile must fail
    here, not as a silent mis-aligned ladder at plan-build time."""
    make_kernel(name)            # validate eagerly
    if impl not in _IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; have {_IMPLS}")
    if block_q is not None and block_q < 1:
        raise ValueError(f"block_q must be a positive tile size; got "
                         f"{block_q}")
    return KernelSpec(name, impl, fused, block_q)


def kdiag(kfn: KernelFn, params: dict, X: jax.Array) -> jax.Array:
    """diag k(X, X) without forming the matrix (O(n·d))."""
    if isinstance(kfn, KernelSpec):
        return kfn.diag(params, X)
    return jax.vmap(lambda x: kfn(params, x[None], x[None])[0, 0])(X)


def add_noise(K: jax.Array, params: dict) -> jax.Array:
    """K + sigma_n^2 I — the paper's delta_xx' noise term (square K only)."""
    return K + noise_var(params) * jnp.eye(K.shape[-1], dtype=K.dtype)
