"""pICF-based GP — parallel incomplete Cholesky factorization GP (Sec. 4).

Step 2's row-based parallel ICF (Chang et al. 2007) is adapted to the TPU
mesh: the rank loop is a ``lax.fori_loop``; per iteration the global pivot is
an all-reduce argmax and the pivot's feature vector / factor column are
broadcast as masked psums (owner contributes, others contribute zeros) — the
collective realization of the MPI pivot broadcast. Communication per step is
O(d + R); O(R(d+R)) total, matching Table 1's O(R^2 log M) summary term.

Steps 3-6 (eqs. 19-27) then need one psum of (R, R+1+u') quantities and an
R x R solve. The fit/predict split (core/api.py) caches the expensive parts —
the rank-R factor F and the R-space solves Phi_L / ydd (eqs. 21-22) — in an
``api.PICFState``; ``predict_batch`` only recomputes the query-dependent
Sigma-dot (eq. 20) and predictive combine (eqs. 24-27). Prediction layouts:

* ``predict_batch``           — centralized combine from the cached state
  (U replicated; what ``predict`` and the serving path use);
* ``machine_step``            — fully-collective, U replicated (Defs. 8-9);
* ``machine_step_sharded_u``  — U sharded over machines (the Remark after
  Def. 7): Sigma-dot chunks are exchanged with ``lax.all_to_all`` and the
  predictive components combined with ``lax.psum_scatter``, cutting the
  per-machine collective payload from O(R|U|) to O(R|U|/M).

Zero prior mean assumed.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core import covariance as cov
from repro.core import linalg
from repro.core.gp import GPPosterior
from repro.core.ppitc import ParallelPosterior
from repro.parallel.runner import Runner


class ICFLocal(NamedTuple):
    F: jax.Array         # (R, b) this machine's factor columns
    residual: jax.Array  # (b,)   local diagonal residual
    pivots: jax.Array    # (R, d) pivot INPUTS in selection order (replicated)
    Lp: jax.Array        # (R, R) lower factor at the pivots: chol K_PP
    #                      (replicated) — row i is pivot i's factor column,
    #                      which is what extends the factor to unseen rows


def icf_factor_local(kfn, params, Xm, R: int, *, axis_name) -> ICFLocal:
    """Distributed pivoted incomplete Cholesky of the signal kernel.

    Concatenating the returned F over machines (in machine order) equals the
    centralized ``core.icf.icf_factor`` on concatenated data, pivot-for-pivot
    (Theorem-3 equivalence test).

    The pivot sequence (inputs + triangular factor at the pivots) is
    recorded on the side: for any unseen point x the consistent factor
    column is the forward solve ``Lp f = k(P, x)`` — the streaming
    row-append path of ``PICFStore`` (no rank loop per new block).
    """
    b = Xm.shape[0]
    m_idx = jax.lax.axis_index(axis_name)
    d0 = cov.kdiag(kfn, params, Xm)
    # zeros + 0*d0 marks the carries as device-varying so the shard_map scan
    # carry type-checks (VMA inference); a no-op after fusion.
    vary = 0.0 * d0[0]
    F0 = jnp.zeros((R, b), d0.dtype) + 0.0 * d0[None, :]
    Xp0 = jnp.zeros((R, Xm.shape[1]), d0.dtype) + vary
    Lp0 = jnp.zeros((R, R), d0.dtype) + vary

    def step(i, carry):
        F, d, Xp, Lp = carry
        # --- global pivot selection: argmax over machines of local maxima
        local_max = jnp.max(d)
        local_arg = jnp.argmax(d)
        gmax = jax.lax.all_gather(local_max, axis_name)       # (M,)
        owner = jnp.argmax(gmax)
        dp = jnp.max(gmax)
        is_owner = (owner == m_idx)
        # --- owner broadcasts pivot input x_p and partial column F[:, p]
        xp = jax.lax.psum(jnp.where(is_owner, Xm[local_arg], 0.0), axis_name)
        fp = jax.lax.psum(jnp.where(is_owner, F[:, local_arg], 0.0), axis_name)
        rp = jnp.sqrt(jnp.maximum(dp, 1e-30))
        # pivot i's factor column after this step is (fp, rp, 0...): record
        # it as row i of the pivot-triangle (fp rows >= i are still zero)
        Xp = Xp.at[i].set(xp)
        Lp = Lp.at[i].set(fp.at[i].set(rp))
        # --- local rank-1 update (each machine only touches its columns)
        col = kfn(params, xp[None], Xm)[0]                    # K[p, D_m]
        f = (col - F.T @ fp) / rp
        F = jax.lax.dynamic_update_slice_in_dim(F, f[None], i, axis=0)
        d = jnp.maximum(d - f * f, 0.0)
        d = jnp.where(is_owner, d.at[local_arg].set(0.0), d)
        return F, d, Xp, Lp

    F, d, Xp, Lp = jax.lax.fori_loop(0, R, step, (F0, d0, Xp0, Lp0))
    return ICFLocal(F, d, Xp, Lp)


def _global_pieces(params, Fm, ym, Sdot_m, *, axis_name):
    """Steps 3-4 (eqs. 19-23): fused psum of [Phi_m | ydot_m | Sdot_m]."""
    s2 = cov.noise_var(params)
    R = Fm.shape[0]
    ydot = Fm @ ym                                          # (R,)   eq. 19
    Phi_m = Fm @ Fm.T                                       # (R, R) eq. 21
    # fuse the three all-reduces into one message (overlap-friendly)
    packed = jnp.concatenate(
        [Phi_m, ydot[:, None], Sdot_m], axis=1)             # (R, R+1+u)
    packed = jax.lax.psum(packed, axis_name)
    Phi = jnp.eye(R, dtype=Fm.dtype) + packed[:, :R] / s2
    Phi_L = linalg.chol(Phi, jitter=0.0)
    ydd = linalg.chol_solve(Phi_L, packed[:, R:R + 1])[:, 0]        # eq. 22
    Sdd = linalg.chol_solve(Phi_L, packed[:, R + 1:])               # eq. 23
    return ydd, Sdd


def machine_step(kfn, params, Xm, ym, U, Fm, *, axis_name):
    """Steps 3-6 with replicated U. Returns replicated (mean_U, cov_UU)."""
    s2 = cov.noise_var(params)
    Kud = kfn(params, U, Xm)                                # (u, b)
    Sdot_m = Fm @ Kud.T                                     # (R, u) eq. 20
    ydd, Sdd = _global_pieces(params, Fm, ym, Sdot_m, axis_name=axis_name)
    # eqs. (24)-(25): predictive components; (26)-(27): psum-combine
    mu_m = Kud @ ym / s2 - Sdot_m.T @ ydd / s2**2
    Sig_m = Kud @ Kud.T / s2 - Sdot_m.T @ Sdd / s2**2
    mean = jax.lax.psum(mu_m, axis_name)
    Kuu = kfn(params, U, U)
    covm = Kuu - jax.lax.psum(Sig_m, axis_name)
    return mean, covm


def machine_step_sharded_u(kfn, params, Xm, ym, Ub_all, Fm, *, axis_name):
    """Steps 3-6 with U sharded (Remark after Def. 7), reduce-scatter form.

    ``Ub_all``: (M, u/M, d) — every machine sees the chunk layout of U (cheap:
    inputs only). Machine m computes Sigma-dot against all of U but only
    chunk-sized pieces cross the network:

      * Phi, ydot  — one (R, R+1) all-reduce (the paper's O(R^2 log M));
      * Sdot       — ``psum_scatter``: machine i receives S_i = sum_m
        Sdot_m^{(i)} — exactly the paper's "each machine m sends Sdot_m^i to
        machine i";
      * the cross terms fold algebraically:
            sum_m (Sdot_m^i)^T ydd   = S_i^T ydd
            sum_m (Sdot_m^i)^T Sdd^i = S_i^T Phi^{-1} S_i
        so no machine ever needs the full (R, |U|) global Sigma-dot.

    §Perf (GP cells): this cut pICF collective bytes 302MB -> ~20MB at
    |U| = 32768, R = 2048, M = 256.
    """
    s2 = cov.noise_var(params)
    M, bu, _ = Ub_all.shape
    U = Ub_all.reshape(M * bu, -1)
    m_idx = jax.lax.axis_index(axis_name)
    R = Fm.shape[0]

    Kud = kfn(params, U, Xm)                                # (u, b)
    Sdot_m = Fm @ Kud.T                                     # (R, u)
    ydot_m = Fm @ ym                                        # (R,)
    Phi_m = Fm @ Fm.T                                       # (R, R)

    packed = jax.lax.psum(
        jnp.concatenate([Phi_m, ydot_m[:, None]], axis=1), axis_name)
    Phi_L = linalg.chol(jnp.eye(R, dtype=Fm.dtype) + packed[:, :R] / s2,
                        jitter=0.0)
    ydd = linalg.chol_solve(Phi_L, packed[:, R:])[:, 0]     # eq. 22

    # reduce-scatter the Sdot chunks: machine i gets S_i = sum_m Sdot_m^i
    S_i = jax.lax.psum_scatter(
        Sdot_m.reshape(R, M, bu).transpose(1, 0, 2), axis_name,
        scatter_dimension=0, tiled=False)                   # (R, bu)
    Sdd_i = linalg.chol_solve(Phi_L, S_i)                   # eq. 23, chunk i

    mean_chunk = (jax.lax.psum_scatter(
        (Kud @ ym / s2).reshape(M, bu), axis_name,
        scatter_dimension=0, tiled=False)
        - S_i.T @ ydd / s2**2)                              # eqs. 24/26

    Kud_c = Kud.reshape(M, bu, -1)                          # (M, bu, b)
    blocks = jnp.einsum("mib,mjb->mij", Kud_c, Kud_c) / s2
    Sig_chunk = (jax.lax.psum_scatter(
        blocks, axis_name, scatter_dimension=0, tiled=False)
        - S_i.T @ Sdd_i / s2**2)                            # eqs. 25/27

    Um = Ub_all[m_idx]
    return mean_chunk, kfn(params, Um, Um) - Sig_chunk


def factor(kfn, params, X, R: int, runner: Runner) -> ICFLocal:
    """Distributed ICF over a Runner; returns stacked (M, R, b) factors."""
    Xb = runner.shard_blocks(X)
    fn = lambda Xm, params: icf_factor_local(kfn, params, Xm, R,
                                             axis_name=runner.axis_name)
    return runner.map(fn, (Xb,), (params,))


# ---------------------------------------------------------------------------
# fit -> PosteriorState -> predict_batch (core/api.py architecture)
# ---------------------------------------------------------------------------

def fit(kfn, params, X, y, *, rank: int, runner: Runner) -> api.PICFState:
    """Distributed ICF (the O(R^2 |D|/M) part) + cached R-space solves.

    ``PICFStore`` (below) is the fit-side producer, so cold fits and the
    streaming row-append/retire path share one code path."""
    return init_picf_store(kfn, params, X, y, rank=rank,
                           runner=runner).to_state()


@linalg.f32_matmuls
def predict_batch(kfn, params, state: api.PICFState, U, *,
                  diag_only: bool = False) -> GPPosterior:
    """Eqs. (20), (23)-(27) from the cached factor — no rank loop per query."""
    s2 = cov.noise_var(params)

    def per_m(Xm, ym, Fm):
        Kud = kfn(params, U, Xm)                            # (u, b)
        return Kud @ ym, Fm @ Kud.T, Kud

    Ky, Sdot_m, Kud_m = jax.vmap(per_m)(state.Xb, state.yb, state.F)
    Sdot = jnp.sum(Sdot_m, 0)                               # (R, u) eq. 20
    mean = jnp.sum(Ky, 0) / s2 - Sdot.T @ state.ydd / s2**2  # eqs. 24/26
    Sdd = linalg.chol_solve(state.Phi_L, Sdot)              # eq. 23
    if diag_only:
        var = (cov.kdiag(kfn, params, U)
               - jnp.sum(jnp.einsum("mub,mub->mu", Kud_m, Kud_m), 0) / s2
               + jnp.sum(Sdot * Sdd, 0) / s2**2)
        return GPPosterior(mean, jnp.diag(var))
    Kuu = kfn(params, U, U)
    Sig = jnp.sum(jnp.einsum("mub,mvb->muv", Kud_m, Kud_m), 0) / s2 \
        - Sdot.T @ Sdd / s2**2                              # eqs. 25/27
    return GPPosterior(mean, Kuu - Sig)


@linalg.f32_matmuls
def predict_batch_diag(kfn, params, state: api.PICFState, U):
    """(mean, var) vectors — no |U|x|U| intermediates (serving hot path)."""
    s2 = cov.noise_var(params)

    def per_m(Xm, ym, Fm):
        Kud = kfn(params, U, Xm)                            # (u, b)
        return Kud @ ym, Fm @ Kud.T, jnp.sum(Kud * Kud, axis=1)

    Ky, Sdot_m, K2 = jax.vmap(per_m)(state.Xb, state.yb, state.F)
    Sdot = jnp.sum(Sdot_m, 0)                               # (R, u) eq. 20
    mean = jnp.sum(Ky, 0) / s2 - Sdot.T @ state.ydd / s2**2
    Sdd = linalg.chol_solve(state.Phi_L, Sdot)              # eq. 23
    var = (cov.kdiag(kfn, params, U) - jnp.sum(K2, 0) / s2
           + jnp.sum(Sdot * Sdd, 0) / s2**2)
    return mean, var


def predict(kfn, params, X, y, U, R: int, runner: Runner, *,
            shard_u: bool = False):
    """End-to-end pICF-based GP regression over a Runner.

    The replicated-U layout is a thin wrapper over fit + predict_batch; the
    sharded-U layout stays fully collective (its point is the comm pattern).
    """
    if shard_u:
        Xb, yb = runner.shard_blocks(X), runner.shard_blocks(y)
        local = factor(kfn, params, X, R, runner)
        Ub = runner.shard_blocks(U)
        fn = lambda Xm, ym, Fm, params, Ub_all: machine_step_sharded_u(
            kfn, params, Xm, ym, Ub_all, Fm, axis_name=runner.axis_name)
        means, covs = runner.map(fn, (Xb, yb, local.F), (params, Ub))
        return ParallelPosterior(runner.unshard(means), covs)

    state = fit(kfn, params, X, y, rank=R, runner=runner)
    return predict_batch(kfn, params, state, U)


def predict_distributed(kfn, params, X, y, U, R: int, runner: Runner):
    """Fully-collective replicated-U pICF (Defs. 8-9 as written)."""
    Xb, yb = runner.shard_blocks(X), runner.shard_blocks(y)
    local = factor(kfn, params, X, R, runner)
    fn = lambda Xm, ym, Fm, params, U: machine_step(
        kfn, params, Xm, ym, U, Fm, axis_name=runner.axis_name)
    means, covs = runner.map(fn, (Xb, yb, local.F), (params, U))
    # replicated outputs: every machine holds the same full posterior
    return GPPosterior(means[0], covs[0])


# ---------------------------------------------------------------------------
# Incremental state (api.StateStore): row-append / retire on the ICF factor.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PICFStore:
    """pICF's ``api.StateStore`` over the distributed rank-R factor.

    The fit-time pivot basis is FROZEN: a streamed block's factor columns
    are the Nyström-consistent extension ``F_new = Lp^{-1} K_{P,D'}``
    (standard streaming ICF — the same forward solve the rank loop performs
    per pivot, batched over the new rows), so appending b rows costs
    O(R²·b) and the global R-space factor advances by a rank-b Cholesky
    update of ``Phi_L`` (eq. 21) instead of an O(R³) refactorization.
    Retiring a machine downdates by its factor columns — the summary
    algebra of eqs. (19)/(21) is a sum over machines, same as pPITC's.

    Note the retired/streamed posterior lives in the ORIGINAL pivot basis;
    a from-scratch refit would re-pivot greedily. That is the standard
    streaming trade: the basis stays optimal for the fit-time data and
    Nyström-extends to new rows.
    """
    kfn: object
    params: dict
    runner: Runner
    Xb: jax.Array      # (M, b, d)
    yb: jax.Array      # (M, b)
    F: jax.Array       # (M, R, b)
    Xp: jax.Array      # (R, d) pivot inputs
    Lp: jax.Array      # (R, R) pivot triangle (chol K_PP)
    alive: jax.Array   # (M,) bool
    Phi_L: jax.Array   # (R, R) cached chol(I + Σ_alive F_m F_mᵀ / s2)
    yF: jax.Array      # (R,)   cached Σ_alive F_m y_m

    @property
    def block_size(self) -> int:
        return int(self.Xb.shape[1])

    def _scaled(self, Fm: jax.Array) -> jax.Array:
        """Factor columns as Phi update vectors: Phi += (F/σ)(F/σ)ᵀ."""
        return Fm / jnp.sqrt(cov.noise_var(self.params))

    @linalg.f32_matmuls
    def assimilate(self, X_new, y_new,
                   runner: Runner | None = None) -> "PICFStore":
        runner = runner or self.runner
        M_new = runner.num_machines
        b = X_new.shape[0] // M_new
        if X_new.shape[0] % M_new or b != self.block_size:
            raise ValueError(
                f"pICF streaming keeps the fit-time block size: got "
                f"|D'|={X_new.shape[0]} over M={M_new} machines but the "
                f"store's blocks are b={self.block_size}; re-chunk the wave.")
        Xb_new = runner.shard_blocks(X_new)
        yb_new = runner.shard_blocks(y_new)
        # Nyström extension in the frozen pivot basis, one forward solve
        F_new = jax.vmap(lambda Xm: linalg.tri_solve(
            self.Lp, self.kfn(self.params, self.Xp, Xm)))(Xb_new)
        W = jnp.concatenate([self._scaled(f) for f in F_new], axis=1)
        return dataclasses.replace(
            self,
            Xb=jnp.concatenate([self.Xb, Xb_new]),
            yb=jnp.concatenate([self.yb, yb_new]),
            F=jnp.concatenate([self.F, F_new]),
            alive=jnp.concatenate(
                [self.alive, jnp.ones((M_new,), bool)]),
            Phi_L=linalg.chol_update_rank(self.Phi_L, W),
            yF=self.yF + jnp.sum(jnp.einsum("mrb,mb->mr", F_new, yb_new), 0))

    @linalg.f32_matmuls
    def retire(self, machine: int) -> "PICFStore":
        api.check_machine_index(self.alive.shape[0], machine)
        alive = api.concrete_alive_mask(self.alive)
        if alive is None:
            raise TypeError(
                "PICFStore.retire() branches on the alive mask host-side "
                "(the already-retired no-op check) and cannot run under "
                "jit/vmap; retire machines before entering the traced "
                "region")
        if not alive[machine]:
            return self
        return dataclasses.replace(
            self,
            alive=self.alive.at[machine].set(False),
            Phi_L=linalg.chol_update_rank(
                self.Phi_L, self._scaled(self.F[machine]), sign=-1.0),
            yF=self.yF - self.F[machine] @ self.yb[machine])

    @linalg.f32_matmuls
    def revive(self, machine: int) -> "PICFStore":
        api.check_machine_index(self.alive.shape[0], machine)
        alive = api.concrete_alive_mask(self.alive)
        if alive is None:
            raise TypeError(
                "PICFStore.revive() branches on the alive mask host-side "
                "(the already-alive no-op check) and cannot run under "
                "jit/vmap; revive machines before entering the traced "
                "region")
        if alive[machine]:
            return self
        return dataclasses.replace(
            self,
            alive=self.alive.at[machine].set(True),
            Phi_L=linalg.chol_update_rank(
                self.Phi_L, self._scaled(self.F[machine])),
            yF=self.yF + self.F[machine] @ self.yb[machine])

    def to_state(self) -> api.PICFState:
        ydd = linalg.chol_solve(self.Phi_L, self.yF[:, None])[:, 0]  # eq. 22
        alive = api.concrete_alive_mask(self.alive)
        if alive is None or alive.all():
            # streaming common case: pass the block arrays by reference.
            # A TRACED store is all-alive by construction (retire/revive
            # reject traced masks), so this branch is also the only
            # realizable one under jit — the PR-7 to_state bug class,
            # fixed the same way as PICStore.to_state
            return api.PICFState(self.Xb, self.yb, self.F, self.Phi_L, ydd)
        idx = jnp.asarray(np.flatnonzero(alive))
        return api.PICFState(self.Xb[idx], self.yb[idx], self.F[idx],
                             self.Phi_L, ydd)


@linalg.f32_matmuls
def init_picf_store(kfn, params, X, y, *, rank: int,
                    runner: Runner) -> PICFStore:
    """``GPMethod.init_store`` for picf: distributed ICF + cached R-space
    factors, cold-factorized once."""
    Xb, yb = runner.shard_blocks(X), runner.shard_blocks(y)
    local = factor(kfn, params, X, rank, runner)            # (M, R, b)
    s2 = cov.noise_var(params)
    R = local.F.shape[1]
    Phi = jnp.eye(R, dtype=local.F.dtype) \
        + jnp.sum(jnp.einsum("mrb,msb->mrs", local.F, local.F), 0) / s2
    Phi_L = linalg.chol(Phi, jitter=0.0)                    # eq. 21
    yF = jnp.sum(jnp.einsum("mrb,mb->mr", local.F, yb), 0)  # eq. 19
    alive = jnp.ones((runner.num_machines,), bool)
    # pivots/Lp are replicated across machines: take machine 0's copy
    return PICFStore(kfn, params, runner, Xb, yb, local.F,
                     local.pivots[0], local.Lp[0], alive, Phi_L, yF)


api.register(api.GPMethod("picf", fit, predict_fn=predict_batch,
                          predict_diag_fn=predict_batch_diag,
                          init_store=init_picf_store))
