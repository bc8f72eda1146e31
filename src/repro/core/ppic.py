"""pPIC — parallel PIC approximation of FGP (paper Sec. 3, Def. 5, Thm. 2).

Extends pPITC with the worker-local correction: machine m blends the global
summary with exact covariance against its own block (eqs. 12-14), recovering
centralized PIC (Snelson 2007) exactly.

Fit/predict split (core/api.py): ``fit`` caches, per block, the factors the
local correction needs (V = chol(K_SS)^{-1} K_SDm, chol Sigma_{DmDm|S},
C^{-1}y) plus the global S-space factors, in an ``api.PICState``. A
repeated query batch then skips every O(b^3) local Cholesky — only
cross-covariances and cached triangular solves remain. Two query-to-block
assignment policies:

* positional (``predict_batch``/``predict_batch_diag``) — query blocks are
  slices of the batch in arrival order, zero-padded when |U| doesn't divide
  M. Fast, but the posterior of a query depends on where in the batch it sat;
  co-cluster queries first (core/clustering.py, Remark 2) when accuracy
  matters.
* routed (``predict_routed``/``predict_routed_diag``) — each query goes to
  the block whose fit-time centroid it is nearest (Remark 2 realized at
  serving time; centroids are cached in the state). A query's posterior then
  depends only on the query point and the fitted state — invariant to batch
  order and composition (tests/test_routing_equivalence.py) — which is what
  arbitrary-traffic serving needs (launch/gp_serve.py). The diag variant
  serves through the two-bucket capacity layout
  (``runner.scatter_two_bucket``): ~(alpha+1)·|U| computed rows instead of
  the skew-proof-but-padded M·|U| of ``scatter_by_block``, same posteriors.

NB eq. (13) as printed drops a `Phi Sdd^{-1} Phi^T` term; the form implemented
here is re-derived from Theorem 2 (see core/pitc.py) and verified against the
literal PIC oracle in tests/test_equivalence.py.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import no_retrace
from repro.core import api, clustering
from repro.core import covariance as cov
from repro.core import linalg
from repro.core.gp import GPPosterior
from repro.core.ppitc import (ParallelPosterior, local_summary,
                              whitened_summary)
from repro.parallel.runner import (ROUTED_ALPHA, Runner, gather_by_block,
                                   gather_two_bucket, pad_blocks,
                                   routed_capacity, scatter_by_block,
                                   scatter_two_bucket)
from repro.runtime import spans


def machine_step(kfn, params, S, Xm, ym, Um, *, axis_name):
    """Full pPIC per-machine program: steps 2-4 with local correction —
    eqs. (12)-(14) for the machine's query block, through the same
    whitened forms as the cached-state path (``_block_posterior``)."""
    Kss_L = linalg.chol(kfn(params, S, S))
    _, (_, V, C_L, Wy) = local_summary(kfn, params, S, Kss_L, Xm, ym)
    A_L, w = whitened_summary(V, C_L, Wy, axis_name)
    return _block_posterior(kfn, params, api.PITCState(S, Kss_L, A_L, w),
                            Um, (Xm, V, C_L, Wy))


# ---------------------------------------------------------------------------
# fit -> PosteriorState -> predict_batch (core/api.py architecture)
# ---------------------------------------------------------------------------

def fit(kfn, params, X, y, *, S, runner: Runner) -> api.PICState:
    """Steps 1-3 over a Runner + per-block caches for eqs. (12)-(14).

    ``online.PICStore`` is the fit-side producer (one code path for cold
    fits and streamed states, mirroring ppitc.fit): a cold fit is just the
    store's initial ``to_state``.
    """
    from repro.core import online
    return online.init_pic_store(kfn, params, X, y, S=S,
                                 runner=runner).to_state()


def init_store(kfn, params, X, y, *, S, runner: Runner):
    """``api.StateStore`` entry point (online.PICStore): streamed/retired
    blocks keep emitting routed-servable PICStates with fresh centroids."""
    from repro.core import online
    return online.init_pic_store(kfn, params, X, y, S=S, runner=runner)


def _whitened_cross(kfn, params, state: api.PICState, Um, Xm, V):
    """The cross terms of eqs. (12)-(14) for one query block, whitened by
    L = chol K_SS: ``vT = K_{U S} L⁻ᵀ`` and the conditional
    cross-covariance ``R = K_{U D_m} - vT V_m = Σ_{U D_m | S}``.

    In these terms eq. (14) is Φ = K_{U S} - R C⁻¹ K_{D_m S}, i.e.
    Φ L⁻ᵀ = vT - R C⁻¹ V_mᵀ; with zT = Φ L⁻ᵀ A_L⁻ᵀ the mean (12) is
    zT w + R C⁻¹ y_m and the covariance (13) is
    K_UU - vT vTᵀ - R C⁻¹ Rᵀ + zT zTᵀ. The textbook form
    K_US + K_US K_SS⁻¹ Σ̇_SS - Σ̇_US subtracts terms far larger than Φ and
    runs K_SS⁻¹ (condition ~1e5 at |S| = 2,048) through them: in float32 it
    lost the posterior (variance errors ~1e-1 against float64 at |D| =
    8,000, |S| = 2,048); this form only solves against factors whose
    condition is the square root."""
    vT = jax.lax.linalg.triangular_solve(
        state.Kss_L, kfn(params, Um, state.S), left_side=False, lower=True,
        transpose_a=True)                              # (u, s)
    return vT, kfn(params, Um, Xm) - vT @ V


def _global_rows(state: api.PICState, vT, P, V):
    """zT = Φ L⁻ᵀ A_L⁻ᵀ (eq. 14 whitened twice) from the cross terms;
    ``P`` is R C⁻¹. Its rows dot ``w`` to Φ Σ̈⁻¹ ÿ, and its row norms are
    the diagonal of Φ Σ̈⁻¹ Φᵀ."""
    return jax.lax.linalg.triangular_solve(
        state.A_L, vT - P @ V.T, left_side=False, lower=True,
        transpose_a=True)


def _block_posterior(kfn, params, state: api.PICState, Um, m_fields):
    """Eqs. (12)-(14) for one query block from cached factors."""
    Xm, V, C_L, Wy = m_fields
    vT, R = _whitened_cross(kfn, params, state, Um, Xm, V)
    P = linalg.chol_solve_right(C_L, R)                # R C⁻¹
    zT = _global_rows(state, vT, P, V)
    mean = zT @ state.w + R @ Wy                       # eq. (12)
    covm = (kfn(params, Um, Um) - vT @ vT.T - R @ P.T  # eq. (13)
            + zT @ zT.T)
    return mean, covm


def _block_posterior_diag(kfn, params, state: api.PICState, Um, m_fields):
    """Diagonal of eqs. (12)-(13) for one query block, no |U_m|^2 buffers.

    Every contraction keeps the query axis on matrix ROWS (row-wise
    multiply-reduce instead of gemv, right-sided solves instead of a
    left-sided solve on Kᵀ, row-major gemms): XLA picks gemv/trsm/gemm
    panel strategies from the row count and total width, so a query-COLUMN
    formulation is not bitwise stable across slot positions or buffer
    widths — which would break both the routed permutation-invariance
    property and the two-bucket layout's equivalence to the capacity-|U|
    layout (tests/test_routing_equivalence.py). Row-major forms are stable.
    """
    Xm, V, C_L, Wy = m_fields
    vT, R = _whitened_cross(kfn, params, state, Um, Xm, V)
    return _local_diag(kfn, params, state, Um, vT, R,
                       linalg.chol_solve_right(C_L, R), V, Wy)


def _local_diag(kfn, params, state: api.PICState, Um, vT, R, P, V, Wy):
    """(mean, var) of one query block from its whitened cross terms and
    ``P = R C⁻¹`` (see ``_whitened_cross``)."""
    rowdot = lambda A, B: jnp.sum(A * B, axis=1)
    zT = _global_rows(state, vT, P, V)
    mean = rowdot(zT, state.w[None, :]) + rowdot(R, Wy[None, :])
    var = (cov.kdiag(kfn, params, Um) - rowdot(vT, vT) - rowdot(R, P)
           + rowdot(zT, zT))
    return mean, var


def _block_fields(state: api.PICState):
    return (state.Xb, state.V, state.C_L, state.Wy)


@linalg.f32_matmuls
def predict_blocks(kfn, params, state: api.PICState,
                   U) -> ParallelPosterior:
    """Block-layout posterior from cached state (|U| must divide M;
    queries are assigned to blocks in order)."""
    M = state.Xb.shape[0]
    u = U.shape[0]
    if u % M != 0:
        raise ValueError(
            f"|U|={u} must divide M={M} for the block layout; use "
            f"predict_batch/predict_batch_diag for arbitrary batch sizes")
    one = lambda Um, *mf: _block_posterior(kfn, params, state, Um, mf)
    means, covs = jax.vmap(one)(U.reshape((M, u // M) + U.shape[1:]),
                                *_block_fields(state))
    return ParallelPosterior(means.reshape(-1), covs)


@linalg.f32_matmuls
def predict_batch(kfn, params, state: api.PICState, U) -> GPPosterior:
    """Blockwise posterior from cached state for any |U|: pads the query
    batch to the block layout, assembles the dense block-diagonal
    covariance, and trims. (Type-stable; use ``predict_blocks`` when the
    per-machine block layout itself is wanted.)"""
    M = state.Xb.shape[0]
    u = U.shape[0]
    Ub, _ = pad_blocks(U, M)
    one = lambda Um, *mf: _block_posterior(kfn, params, state, Um, mf)
    means, covs = jax.vmap(one)(Ub, *_block_fields(state))
    post = ParallelPosterior(means.reshape(-1), covs)
    return GPPosterior(post.mean[:u], post.cov[:u, :u])


@linalg.f32_matmuls
def predict_batch_diag(kfn, params, state: api.PICState, U):
    """(mean, var) for any |U|: pads to the block layout, trims after."""
    M = state.Xb.shape[0]
    u = U.shape[0]
    Ub, _ = pad_blocks(U, M)
    one = lambda Um, *mf: _block_posterior_diag(kfn, params, state, Um, mf)
    means, vars_ = jax.vmap(one)(Ub, *_block_fields(state))
    return means.reshape(-1)[:u], vars_.reshape(-1)[:u]


# ---------------------------------------------------------------------------
# Routed prediction (Remark 2 at serving time): nearest-centroid assignment.
# ---------------------------------------------------------------------------

def route_queries(state: api.PICState, U) -> jax.Array:
    """(u,) block id per query: nearest fit-time block centroid.

    A pure function of (query point, state), so the induced posterior cannot
    depend on batch order or composition — the serving-side equivalence the
    positional path lacks.
    """
    d2 = jnp.sum((U[:, None, :] - state.centroids[None, :, :]) ** 2, axis=-1)
    return jnp.argmin(d2, axis=1)


def _block_posterior_diag_cinv(kfn, params, state: api.PICState, Um,
                               m_fields, Cinv_m):
    """``_block_posterior_diag`` with the per-block solve served from a
    PRECOMPUTED dense inverse: ``R C⁻¹`` is one row-major gemm instead of
    the two-sided batched triangular solve.

    This is the plan-owned backend cache (``ServeSpec(cached_cinv=True)``):
    XLA-CPU (and small-RHS TPU) batched trsm bills per PROGRAM almost
    independently of the RHS width, so the routed layout's M+G solve
    programs cost more than their row saving — a batched matmul scales with
    the RHS width on every backend. Different float path than the trsm
    (same math, inverse applied multiplicatively), hence opt-in: the
    default serving plan stays bitwise-faithful to the legacy path.
    Row-major throughout for the same composition-invariance reasons as
    ``_block_posterior_diag``.
    """
    Xm, V, C_L, Wy = m_fields
    vT, R = _whitened_cross(kfn, params, state, Um, Xm, V)
    return _local_diag(kfn, params, state, Um, vT, R, R @ Cinv_m, V, Wy)


@no_retrace("ppic.cinv_blocks")
@jax.jit
@linalg.f32_matmuls
def cinv_blocks(C_L: jax.Array) -> jax.Array:
    """(M, b, b) dense symmetric inverses ``(C_L C_Lᵀ)⁻¹`` per block — the
    one-time plan-build cost behind ``ServeSpec(cached_cinv=True)``; every
    routed flush thereafter multiplies instead of solving.

    Under the ``no_retrace`` contract: after a deployment's warmup
    ``contracts.freeze()``, a rebind/refresh must only ever call this with
    already-seen (M, b, b) signatures — a new signature mid-serving is a
    silent recompile the audit flags."""
    eye = jnp.eye(C_L.shape[-1], dtype=C_L.dtype)
    return jax.vmap(lambda L: linalg.chol_solve(L, eye))(C_L)


def _routed_diag_program(kfn, params, state: api.PICState, Cinv, U,
                         assign=None, *, alpha: int, tile: int,
                         n_groups: int | None):
    """The routed serving program body: two-bucket scatter -> per-block
    posterior -> gather, parameterized by the overflow-group count and the
    optional C⁻¹ backend cache. ``predict_routed_diag`` is this program at
    its worst-case defaults (assignment derived on device); ``PICServePlan``
    jits one instance per selected group count (lazy overflow dispatch) and
    passes its host-computed ``assign`` in as a traced argument — the SAME
    assignment that sized the group count, so the scatter can never see a
    row the selection did not provision for (a device-side re-derivation
    could flip a near-boundary argmin across float paths and silently drop
    the flipped row past the chosen capacity)."""
    M = state.Xb.shape[0]
    if assign is None:
        assign = route_queries(state, U)
    lay = scatter_two_bucket(U, assign, M, alpha=alpha, tile=tile,
                             max_groups=n_groups)
    if Cinv is None:
        one = lambda Um, *mf: _block_posterior_diag(kfn, params, state,
                                                    Um, mf)
        means, vars_ = jax.vmap(one)(lay.Xb, *_block_fields(state))
    else:
        one = lambda Um, Ci, *mf: _block_posterior_diag_cinv(
            kfn, params, state, Um, mf, Ci)
        means, vars_ = jax.vmap(one)(lay.Xb, Cinv, *_block_fields(state))
    means_o = vars_o = None
    if lay.Xo is not None:
        # overflow groups: gather the owning block's cached factors per
        # group (dynamic indices, static shapes — jit-safe)
        mf_o = tuple(a[lay.o_blk] for a in _block_fields(state))
        if Cinv is None:
            means_o, vars_o = jax.vmap(one)(lay.Xo, *mf_o)
        else:
            means_o, vars_o = jax.vmap(one)(lay.Xo, Cinv[lay.o_blk], *mf_o)
    return (gather_two_bucket(means, means_o, lay),
            gather_two_bucket(vars_, vars_o, lay))


def global_diag(kfn, params, state: api.PICState, U):
    """The pPITC (eqs. 7-8) diag posterior from a PIC state's GLOBAL
    factors only — no per-block cache touched.

    ``PICState``'s first four fields ARE a ``PITCState`` (the S-space
    summary the local corrections refine), so a query whose nearest block
    is unavailable can still be answered from the global posterior: a
    strictly coarser approximation (PIC minus its local correction), never
    an error and never a NaN from the dead block's factors. This is the
    bounded-degradation serving path — accuracy degrades to pPITC, bounded
    by the ``with_alive`` refit oracle (tests/test_resilience.py)."""
    from repro.core import ppitc
    gstate = api.PITCState(state.S, state.Kss_L, state.A_L, state.w)
    return ppitc.predict_batch_diag(kfn, params, gstate, U)


def _routed_deg_program(kfn, params, state: api.PICState, Cinv, U, assign,
                        dead_row, *, alpha: int, tile: int,
                        n_groups: int | None):
    """``_routed_diag_program`` with per-row bounded degradation.

    ``dead_row`` is a (|U|,) bool TRACED value (not a shape), so one
    compiled program serves every failure pattern — which block died, and
    how many rows it strands, never triggers a recompile (the acceptance
    criterion the health layer's auto-retire leans on). Rows whose target
    block is dead are answered from the global S-space posterior via a
    per-row select; the select also firewalls NaN/Inf a poisoned block's
    factors may have produced, since ``jnp.where`` never propagates the
    unselected branch's values."""
    mean_r, var_r = _routed_diag_program(kfn, params, state, Cinv, U, assign,
                                         alpha=alpha, tile=tile,
                                         n_groups=n_groups)
    mean_g, var_g = global_diag(kfn, params, state, U)
    return (jnp.where(dead_row, mean_g, mean_r),
            jnp.where(dead_row, var_g, var_r))


@linalg.f32_matmuls
def predict_routed_diag(kfn, params, state: api.PICState, U, *,
                        alpha: int = ROUTED_ALPHA, tile: int | None = None):
    """Batch-composition-invariant (mean, var) for any |U|.

    Scatters the batch to nearest-centroid blocks through the two-bucket
    capacity scheme (``runner.scatter_two_bucket``): a (M, alpha*ceil(|U|/M))
    main bucket plus a static set of skew-overflow groups, each served with
    its recorded block's cached factors. Shapes — and the compiled
    executable — still depend only on (|U|, M), but balanced traffic pays
    ~(alpha+1)*|U| computed rows instead of the capacity-|U| layout's M*|U|.
    Per-row posteriors are bitwise identical to that layout (every
    predictive equation is row-independent; tests/test_routing_equivalence).

    ``tile`` aligns the bucket width to the serving kernel's block_q so the
    Pallas dispatch needs no second pad. This is the worst-case-G, no-cache
    instance of the serving program; a ``PICServePlan`` additionally selects
    smaller overflow programs from the flush occupancy and can serve the
    per-block solve from cached C⁻¹ (``GPMethod.plan``).
    """
    if tile is None:   # a KernelSpec declares its serving tile; bare kfns: 1
        tile = getattr(kfn, "block_q", None) or 1
    return _routed_diag_program(kfn, params, state, None, U, None,
                                alpha=alpha, tile=tile, n_groups=None)


@linalg.f32_matmuls
def predict_routed_diag_capacity(kfn, params, state: api.PICState, U):
    """Capacity-|U| routed reference (the pre-two-bucket layout): every block
    gets a (|U|,)-slot buffer via ``scatter_by_block``. Kept as the oracle
    the two-bucket path is property-tested against (bitwise) and for the
    bench's padded-rows comparison; ``predict_routed`` still uses this
    layout for its dense within-block covariance view."""
    M = state.Xb.shape[0]
    assign = route_queries(state, U)
    Ub, order, block_of, slot = scatter_by_block(U, assign, M)
    one = lambda Um, *mf: _block_posterior_diag(kfn, params, state, Um, mf)
    means, vars_ = jax.vmap(one)(Ub, *_block_fields(state))
    return (gather_by_block(means, order, block_of, slot),
            gather_by_block(vars_, order, block_of, slot))


@linalg.f32_matmuls
def predict_routed(kfn, params, state: api.PICState, U) -> GPPosterior:
    """Routed posterior with the dense within-block covariance view.

    Mean/variance are the routed per-query values; covariance entries are
    filled for query pairs routed to the same block (eqs. 12-14) and zero
    across blocks — the routed analogue of ``predict_batch``'s
    block-diagonal dense view.
    """
    M = state.Xb.shape[0]
    assign = route_queries(state, U)
    Ub, order, block_of, slot = scatter_by_block(U, assign, M)
    one = lambda Um, *mf: _block_posterior(kfn, params, state, Um, mf)
    means, covs = jax.vmap(one)(Ub, *_block_fields(state))
    mean = gather_by_block(means, order, block_of, slot)
    slot_q = jnp.zeros_like(slot).at[order].set(slot)   # slot in caller order
    same = assign[:, None] == assign[None, :]
    covm = jnp.where(same,
                     covs[assign[:, None], slot_q[:, None], slot_q[None, :]],
                     jnp.zeros((), covs.dtype))
    return GPPosterior(mean, covm)


def predict(kfn, params, S, X, y, U, runner: Runner) -> ParallelPosterior:
    """End-to-end pPIC: thin wrapper over fit + predict_blocks.

    For best accuracy X/U should be co-clustered first
    (core/clustering.py — Remark 2 after Def. 5).
    """
    state = fit(kfn, params, X, y, S=S, runner=runner)
    return predict_blocks(kfn, params, state, U)


# ---------------------------------------------------------------------------
# PICServePlan — the PIC family's phase-1 serving program (api.GPMethod.plan).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PICServePlan(api.ServePlan):
    """``api.ServePlan`` with the two PIC-specific assets the plan/execute
    split exists for:

    * backend caches — ``caches`` holds the per-block dense ``C⁻¹`` when
      the spec asks for it (``cached_cinv=True``), passed to executables as
      a traced argument and recomputed on ``rebind`` — so hot-swapping a
      streamed state refreshes the cache with zero recompilation;
    * a routed executable LADDER — one jitted program per overflow-group
      count g ∈ {0, 1, 2, 4, ..., G_worst}, selected per flush from the
      host-side occupancy: balanced traffic runs the G=0 program (main
      bucket only — no overflow compute dispatched at all), mild skew runs
      a 1-2 group program, and only adversarial skew pays the worst case.
      The selection is EXACT (counts, not a guess): a row past the chosen
      program's capacity would be silently dropped by the scatter, so the
      plan never under-provisions.

    Per-row posteriors are bitwise-identical across the ladder: group k's
    rows run the same row-independent per-block program wherever the batch
    composition lands them (property-tested in tests/test_plan.py).
    """

    def _rebuild_caches(self, state):
        return cinv_blocks(state.C_L) if self.spec.cached_cinv else None

    def _routed_exec(self, g: int):
        kfn, alpha, tile = self.kfn, self.spec.alpha, self.block_q
        return self._jitted(
            ("routed", g), lambda: lambda params, state, caches, U, assign:
                _routed_diag_program(kfn, params, state, caches, U, assign,
                                     alpha=alpha, tile=tile, n_groups=g))

    def _routed_deg_exec(self, g: int):
        """The degraded-dispatch sibling of ``_routed_exec``: same program
        plus a per-row global-posterior select keyed on a traced dead-row
        mask (``_routed_deg_program``). A separate ladder key so healthy
        flushes keep running the bitwise-unchanged baseline program."""
        kfn, alpha, tile = self.kfn, self.spec.alpha, self.block_q
        return self._jitted(
            ("routed_deg", g),
            lambda: lambda params, state, caches, U, assign, dead:
                _routed_deg_program(kfn, params, state, caches, U, assign,
                                    dead, alpha=alpha, tile=tile,
                                    n_groups=g))

    def routed_diag(self, U, block_alive=None):
        """Batch-composition-invariant (mean, var): pad to the bucket
        ladder, route host-side, pick the overflow program from the
        occupancy, dispatch.

        The host-side nearest-centroid assignment of the STAGED padded
        batch is authoritative for BOTH the group-count selection and the
        device scatter (it is passed into the executable as a traced
        argument): one float path, so the program the occupancy sized is
        by construction sufficient for the rows the scatter places.

        Pad rows are NOT routed by centroid — they are packed into blocks
        with spare main-bucket capacity. Every row must land somewhere
        (the scatter's drop semantics would otherwise demand provisioning
        for them), but letting zeros route naturally would pile them onto
        one block and drag partially-filled flushes — the deadline-trigger
        common case — onto the worst-case overflow program. Spare capacity
        always covers them (M·cap >= alpha·ceil(bucket/M)·M >= bucket for
        alpha >= 1), pads sit positionally AFTER the real rows so they can
        never displace a real row's (block, slot) placement, and their
        outputs are trimmed — so overflow demand is the REAL rows' demand,
        and balanced traffic runs G=0 regardless of padding.

        ``block_alive`` (optional (M,) bool) is the health layer's routing
        mask: rows whose nearest-centroid block is marked dead are answered
        from the global S-space posterior instead (``global_diag``) through
        the degraded executable ladder — same shapes, mask passed as a
        traced value, zero recompiles once warmed. Which rows degraded is
        surfaced via ``stats.last_degraded`` (None on fully-healthy
        flushes, where the bitwise-unchanged baseline program runs)."""
        if isinstance(U, jax.core.Tracer):
            raise TypeError(
                "routed_diag stages on the host (nearest-centroid routing "
                "and pad-packing pick data-dependent programs) and cannot "
                "run under jit/vmap; call it with concrete batches, or "
                "use plan.diag for the traceable unrouted path")
        with spans.span("plan.pad"):
            Up, u = self._padded(U)
        with spans.span("plan.route"):
            assign, g = self._route(np.asarray(Up), u)
        self.stats.last_degraded = None
        dead = None
        if block_alive is not None:
            alive = np.asarray(block_alive, bool)
            M = int(self.state.Xb.shape[0])
            if alive.shape != (M,):
                raise ValueError(
                    f"block_alive must be an ({M},) bool mask over the "
                    f"state's blocks; got shape {alive.shape}")
            dead = ~alive[assign]
        with spans.span("plan.exec"):
            if dead is not None and dead.any():
                mean, var = self._routed_deg_exec(g)(
                    self.params, self.state, self.caches, Up, assign, dead)
                self.stats.last_degraded = dead[:u].copy()
                self.stats.n_degraded_rows += int(dead[:u].sum())
            else:
                mean, var = self._routed_exec(g)(self.params, self.state,
                                                 self.caches, Up, assign)
        self.stats.n_routed_batches += 1
        self.stats.last_g = g
        if g == 0:
            self.stats.n_g0_batches += 1
        with spans.span("plan.trim"):
            return mean[:u], var[:u]

    def _route(self, Up: np.ndarray, u: int) -> tuple[np.ndarray, int]:
        """(assign, g) for a staged padded batch whose first ``u`` rows are
        real — the ONE host-side routing decision behind ``routed_diag``
        (and the bench's executable-level timings, which must provision
        exactly what a real flush would)."""
        M = int(self.state.Xb.shape[0])
        assign = clustering.nearest_center_np(
            Up[:u], np.asarray(self.state.centroids)).astype(np.int32)
        counts = np.bincount(assign, minlength=M)
        cap, G_full = routed_capacity(Up.shape[0], M, alpha=self.spec.alpha,
                                      tile=self.block_q)
        pad = Up.shape[0] - u
        if pad:
            spare = (cap - np.minimum(counts, cap)).astype(np.int64)
            pad_assign = np.repeat(np.arange(M, dtype=np.int32),
                                   spare)[:pad]
            assert pad_assign.shape[0] == pad   # M*cap >= bucket invariant
            assign = np.concatenate([assign, pad_assign])
        g = 0
        if G_full:
            over = np.maximum(counts - cap, 0)
            g = _snap_groups(int(np.sum(-(-over // cap))), G_full,
                             self.spec.max_overflow_groups)
        return assign, g

    def warmup(self, d: int, *, dtype=np.float32,
               degraded: bool = True) -> "PICServePlan":
        """Pre-compile the FULL routed executable ladder per bucket — every
        (bucket, g) program a flush can select — so g-selection never pays
        a mid-serving compile (the p99 simulation in bench_serve_latency
        charges real flush time to tickets and would see it).

        ``degraded=True`` (default) additionally compiles the degraded
        sibling of every (bucket, g) program: a block failing MID-STREAM
        must not cost a compile on the first stranded flush (the dead-row
        mask is a traced value, so one degraded program per (bucket, g)
        covers every failure pattern). Pass ``degraded=False`` to halve
        warmup time on deployments that run without the health layer."""
        if not self.spec.routed:
            return super().warmup(d, dtype=dtype)
        M = int(self.state.Xb.shape[0])
        for b in self.buckets or ():
            U0 = np.zeros((b, d), dtype)
            _, G = routed_capacity(b, M, alpha=self.spec.alpha,
                                   tile=self.block_q)
            gs, g = {0, G}, 1
            while g < G:                      # the _snap_groups ladder
                gs.add(g)
                g *= 2
            if self.spec.max_overflow_groups is not None:
                gs = {g for g in gs
                      if g <= self.spec.max_overflow_groups} | {G}
            a0 = np.zeros((b,), np.int32)
            d0 = np.zeros((b,), bool)
            for g in sorted(gs):
                jax.block_until_ready(self._routed_exec(g)(
                    self.params, self.state, self.caches, U0, a0)[0])
                if degraded:
                    jax.block_until_ready(self._routed_deg_exec(g)(
                        self.params, self.state, self.caches, U0, a0,
                        d0)[0])
        return self


def _snap_groups(needed: int, G_full: int, max_groups: int | None) -> int:
    """Snap an exact group demand onto the executable ladder {0, 1, 2, 4,
    ...}: bounded compile count (log G programs) without ever serving a
    program too small for the flush. Demands above ``max_groups`` fall back
    to the always-sufficient worst-case program."""
    if needed <= 0:
        return 0
    g = 1
    while g < needed:
        g *= 2
    if max_groups is not None and g > max_groups:
        return G_full
    return min(g, G_full)


def make_plan(method: api.GPMethod, kfn, params, state: api.PICState,
              spec: api.ServeSpec) -> PICServePlan:
    """``GPMethod.plan_fn`` for ppic/pic."""
    plan = PICServePlan(method, spec.resolve_kfn(kfn), params, state, spec,
                        spec.resolve_block_q(kfn), spec.resolve_buckets(kfn))
    if spec.cached_cinv:
        plan = dataclasses.replace(plan,
                                   caches=plan._rebuild_caches(state))
    return plan


def predict_distributed(kfn, params, S, X, y, U,
                        runner: Runner) -> ParallelPosterior:
    """Fully-collective pPIC (psum inside the per-machine program)."""
    Xb, yb, Ub = (runner.shard_blocks(a) for a in (X, y, U))
    fn = lambda Xm, ym, Um, params, S: machine_step(
        kfn, params, S, Xm, ym, Um, axis_name=runner.axis_name)
    means, covs = runner.map(fn, (Xb, yb, Ub), (params, S))
    return ParallelPosterior(runner.unshard(means), covs)


api.register(api.GPMethod("ppic", fit, predict_fn=predict_batch,
                          predict_diag_fn=predict_batch_diag,
                          predict_routed_diag_fn=predict_routed_diag,
                          init_store=init_store, plan_fn=make_plan))
