"""pPITC — parallel PITC approximation of FGP (paper Sec. 3, Defs. 1-4).

Per-machine program (runs under VmapRunner or ShardMapRunner):

  Step 1  data arrives block-sharded: machine m holds (D_m, y_{D_m});
  Step 2  local summary  (eqs. 3-4)  — O((|D|/M)^3) local cholesky;
  Step 3  global summary (eqs. 5-6)  — ONE all-reduce of an |S|-vector and an
          |S|x|S| matrix (lax.psum == the master-free assimilation; comm
          O(|S|^2 log M) as in Table 1);
  Step 4  each machine predicts its U_m slice (eqs. 7-8) locally.

Fit/predict split (core/api.py): ``fit`` runs steps 1-3 through a Runner and
caches the S-space factors in an ``api.PITCState``, whitened by
L = chol K_SS: Kss_L, A_L = chol(L^{-1} Sdd L^{-T}) and the weights
w = A_L^{-1} L^{-1} ydd; ``predict_batch`` is then O(|U||S|^2) per query
batch with no |S|^3 work — the real-time path. Sdd itself has condition
~1e9 at the paper's scale (|D| = 32,000, |S| = 2,048), past a float32
Cholesky; its whitened form has eigenvalues >= 1. ``predict`` (legacy one-shot) is a thin wrapper
over the two; ``predict_distributed`` keeps the fully-collective execution
where prediction itself must stay on-device.

Unlike pPIC, pPITC needs no routed serving variant: eqs. (7)-(8) touch only
the global S-space factors, so a query's posterior is already independent of
which machine evaluates it — ``predict_blocks`` is pure layout. The
``GPMethod`` therefore registers with ``predict_routed_diag_fn=None``; a
``GPServer(routed=True)`` rejects it at construction, ``ServePlan.
routed_diag`` raises, and the plain diag path already carries the
invariance routing buys (see ppic.predict_routed for the block-sensitive
case).

Zero prior mean assumed (data pipeline centers y).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import api
from repro.core import covariance as cov
from repro.core import linalg
from repro.core.gp import GPPosterior
from repro.parallel.runner import Runner
from repro.runtime import spans


class LocalSummary(NamedTuple):
    """(eqs. 3-4) restricted to B = B' = S — what crosses the network."""
    ydot: jax.Array   # (s,)    y-dot_S^m
    Sdot: jax.Array   # (s, s)  Sigma-dot_SS^m


class GlobalSummary(NamedTuple):
    """(eqs. 5-6)."""
    ydd: jax.Array    # (s,)
    Sdd: jax.Array    # (s, s)  ( = K_SS + sum_m Sdot^m )


class ParallelPosterior(NamedTuple):
    """Block posterior: machine m owns mean/cov of its U_m slice."""
    mean: jax.Array      # (u,)
    blocks: jax.Array    # (M, u/M, u/M) diagonal covariance blocks

    @property
    def var(self) -> jax.Array:
        M, b, _ = self.blocks.shape
        return jax.vmap(jnp.diag)(self.blocks).reshape(M * b)

    @property
    def cov(self) -> jax.Array:   # dense block-diagonal view (small U only)
        return jax.scipy.linalg.block_diag(
            *[self.blocks[m] for m in range(self.blocks.shape[0])])


def local_summary(kfn, params, S, Kss_L, Xm, ym):
    """Eqs. (3)-(4) with B=B'=S. Also returns the pieces pPIC/hyper reuse:
    (Ksd, V = Kss_L^{-1} K_SD_m, C_L = chol Sigma_{DmDm|S},
    Wy = C^{-1} y_m)."""
    Ksd = kfn(params, S, Xm)                          # (s, b)
    V = linalg.tri_solve(Kss_L, Ksd)                  # Kss^{-1/2} K_SD_m
    Kdd = cov.add_noise(kfn(params, Xm, Xm), params)
    C_L = linalg.chol(Kdd - V.T @ V)                  # chol Sigma_{DmDm|S}
    Wy = linalg.chol_solve(C_L, ym[:, None])[:, 0]    # C^{-1}(y - mu)
    ydot = Ksd @ Wy
    Sdot = Ksd @ linalg.chol_solve(C_L, Ksd.T)
    return LocalSummary(ydot, Sdot), (Ksd, V, C_L, Wy)


def whitened_factor(V, C_L):
    """One machine's G = L^{-1} F = V C_L^{-T} from its ``local_summary``
    pieces (V = L^{-1} K_SD, C_L = chol Sigma_{DD|S}, L = chol K_SS), so
    that Sdot = L G G^T L^T."""
    return linalg.tri_solve(C_L, V.T).T


def whitened_summary(V, C_L, Wy, axis_name):
    """Eqs. (5)-(6) whitened by L = chol K_SS, from one machine's
    ``local_summary`` pieces: (A_L, w) with A_L = chol(I + sum_m G_m G_m^T)
    (L^{-1} Sdd L^{-T} = I + sum_m G_m G_m^T) and w = A_L^{-1} sum_m V_m Wy_m
    (= A_L^{-1} L^{-1} ydd). The single all-reduce of the algorithm: one
    |S|x|S| matrix and one |S|-vector."""
    G = whitened_factor(V, C_L)                       # (s, b)
    A = jnp.eye(V.shape[0], dtype=V.dtype) + jax.lax.psum(G @ G.T, axis_name)
    A_L = jnp.linalg.cholesky(A)
    return A_L, linalg.tri_solve(A_L, jax.lax.psum(V @ Wy, axis_name))


def machine_step(kfn, params, S, Xm, ym, Um, *, axis_name):
    """Full pPITC per-machine program: steps 2-4. Returns (mean_m, cov_m)."""
    Kss_L = linalg.chol(kfn(params, S, S))
    _, (_, V, C_L, Wy) = local_summary(kfn, params, S, Kss_L, Xm, ym)
    A_L, w = whitened_summary(V, C_L, Wy, axis_name)
    post = _posterior(kfn, params, api.PITCState(S, Kss_L, A_L, w), Um)
    return post.mean, post.cov


def _whitened_rows(kfn, params, state: api.PITCState, U):
    """Query rows whitened: ``vT = K_US L^{-T}`` and ``zT = vT A_L^{-T}``,
    so eq. (7) is zT w and eq. (8) is K_UU - vT vT^T + zT zT^T. Right-sided
    solves keep the query axis on rows."""
    solve = lambda Lf, B: jax.lax.linalg.triangular_solve(
        Lf, B, left_side=False, lower=True, transpose_a=True)
    vT = solve(state.Kss_L, kfn(params, U, state.S))  # (u, s)
    return vT, solve(state.A_L, vT)


def _posterior(kfn, params, state: api.PITCState, U) -> GPPosterior:
    vT, zT = _whitened_rows(kfn, params, state, U)
    return GPPosterior(zT @ state.w,
                       kfn(params, U, U) - vT @ vT.T + zT @ zT.T)


# ---------------------------------------------------------------------------
# fit -> PosteriorState -> predict_batch (core/api.py architecture)
# ---------------------------------------------------------------------------

def fit(kfn, params, X, y, *, S, runner: Runner) -> api.PITCState:
    """Steps 1-3 over a Runner, cached as an ``api.PITCState``.

    ``online.SummaryStore`` is the fit-side producer: the same per-machine
    summaries that support streaming assimilation (Sec. 5.2) are assembled
    into the cached S-space factors here, so online updates and cold fits
    share one code path.
    """
    from repro.core import online
    with spans.span("fit.ppitc"):
        return online.to_state(online.build(kfn, params, S, X, y, runner), S)


@linalg.f32_matmuls
def predict_batch(kfn, params, state: api.PITCState, U) -> GPPosterior:
    """Eqs. (7)-(8) from cached factors: no |S|^3 work per call."""
    return _posterior(kfn, params, state, U)


@linalg.f32_matmuls
def predict_batch_diag(kfn, params, state: api.PITCState, U):
    """(mean, var) without forming the |U|x|U| posterior covariance.

    The serving hot path: with a ``cov.KernelSpec`` declaring a Pallas
    implementation, the whole computation — K_US tile, both cached
    triangular solves, and the variance quadratic form — collapses into the
    fused ``xcov_diag`` kernel (kernels/rbf/xcov.py) and the (|U|, |S|)
    cross-covariance never round-trips to HBM. The compose path below is
    the math it is validated against (tests/test_xcov_fused.py).
    """
    if isinstance(kfn, cov.KernelSpec) and kfn.fuse(state.S.shape[0]):
        return kfn.fused_diag(params, U, state.S, state.Kss_L, state.w,
                              A2=state.A_L)
    vT, zT = _whitened_rows(kfn, params, state, U)
    rowdot = lambda A: jnp.sum(A * A, axis=1)
    return (zT @ state.w,
            cov.kdiag(kfn, params, U) - rowdot(vT) + rowdot(zT))


@linalg.f32_matmuls
def predict_blocks(kfn, params, state: api.PITCState, U,
                   M: int) -> ParallelPosterior:
    """Per-machine prediction layout (step 4) from the cached state."""
    u = U.shape[0]
    post = jax.vmap(lambda Um: _posterior(kfn, params, state, Um))(
        U.reshape(M, u // M, -1))
    return ParallelPosterior(post.mean.reshape(u), post.cov)


def predict(kfn, params, S, X, y, U, runner: Runner) -> ParallelPosterior:
    """End-to-end pPITC: thin wrapper over fit + predict_blocks."""
    state = fit(kfn, params, X, y, S=S, runner=runner)
    return predict_blocks(kfn, params, state, U, runner.num_machines)


def predict_distributed(kfn, params, S, X, y, U,
                        runner: Runner) -> ParallelPosterior:
    """Fully-collective pPITC (psum inside the per-machine program) — the
    execution the paper describes; kept for on-device end-to-end runs."""
    Xb, yb, Ub = runner.shard_blocks(X), runner.shard_blocks(y), \
        runner.shard_blocks(U)
    fn = lambda Xm, ym, Um, params, S: machine_step(
        kfn, params, S, Xm, ym, Um, axis_name=runner.axis_name)
    means, covs = runner.map(fn, (Xb, yb, Ub), (params, S))
    return ParallelPosterior(runner.unshard(means), covs)


@linalg.f32_matmuls
def summaries(kfn, params, S, X, y, runner: Runner):
    """Stacked per-machine local summaries + the global summary.

    Exposed for online/incremental learning (Sec. 5.2) and fault tolerance:
    the global summary is an algebraic sum, so machine loss/addition is a
    subtraction/addition of cached LocalSummary terms (runtime/fault.py).
    """
    Xb, yb = runner.shard_blocks(X), runner.shard_blocks(y)

    def fn(Xm, ym, params, S):
        Kss_L = linalg.chol(kfn(params, S, S))
        local, _ = local_summary(kfn, params, S, Kss_L, Xm, ym)
        return local

    locals_ = runner.map(fn, (Xb, yb), (params, S))
    Kss = kfn(params, S, S)
    glob = GlobalSummary(jnp.sum(locals_.ydot, 0),
                         Kss + jnp.sum(locals_.Sdot, 0))
    return locals_, glob


def init_store(kfn, params, X, y, *, S, runner: Runner):
    """``api.StateStore`` entry point: the same summaries ``fit`` builds,
    kept mutable via the Sec. 5.2 algebra (online.PITCStore)."""
    from repro.core import online
    return online.init_pitc_store(kfn, params, X, y, S=S, runner=runner)


api.register(api.GPMethod("ppitc", fit, predict_fn=predict_batch,
                          predict_diag_fn=predict_batch_diag,
                          init_store=init_store))
