"""Online/incremental learning (Sec. 5.2) + summary-algebra fault tolerance.

The pPITC/pPIC global summary (eqs. 5-6) is an algebraic SUM of per-machine
local summaries, so:

* new data blocks fold in with an add (no recompute of old blocks' O(b^3)
  inverses) — the paper's streaming argument;
* a failed machine folds OUT with a subtract — survivors' work is preserved
  and the posterior remains a *valid* PITC/PIC posterior over the surviving
  data (runtime/fault.py builds on this);
* elastic scale-up/down is re-blocking + re-summing cached summaries.

Two layers here:

* ``SummaryStore`` — the pure-array pytree of stacked per-machine summaries
  (cheap: M x (|S| + |S|² + |S|·b)) PLUS the incrementally-maintained global
  factor, kept whitened by L = chol K_SS. Every local summary Σ-dot_SS^m is
  PSD with the explicit low-rank factor F_m = K_SDm chol(Σ_{DmDm|S})^{-T}
  (Σ-dot^m = F_m F_mᵀ); the store keeps G_m = L⁻¹F_m, so Σ-dot-dot =
  L (I + Σ_m G_m G_mᵀ) Lᵀ and folding a machine in/out is a rank-b Cholesky
  update/downdate of ``A_L = chol(I + Σ_m G_m G_mᵀ)``
  (``linalg.chol_update_rank``) — O(|S|²·b) instead of a
  re-factorization, which makes ``to_state`` an O(|S|²) solve. Σ-dot-dot
  itself has condition ~1e9 at the paper's scale (|D| = 32,000, |S| =
  2,048), past a float32 Cholesky; A's eigenvalues are >= 1.
* ``PITCStore`` / ``PICStore`` — the method-owned ``api.StateStore``
  implementations (registered via ``GPMethod.init_store`` by core/ppitc.py
  and core/ppic.py). ``PITCStore`` emits ``api.PITCState``; ``PICStore``
  additionally carries the per-block caches of eqs. (12)-(14) and emits
  ``api.PICState`` with alive-block selection and centroid refresh, so
  ``GPServer(routed=True)`` hot-swaps streamed data too.

The module-level free functions (``build``/``assimilate``/``retire``/
``revive``/``to_state``/``predict_ppitc``) are the underlying SummaryStore
algebra; prefer the ``api.StateStore`` protocol (``api.init_store``) in new
code — the free functions survive as the implementation + back-compat
surface for callers that hold a bare ``SummaryStore``.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api, clustering, linalg
from repro.core.ppitc import (GlobalSummary, LocalSummary, local_summary,
                              predict_batch, whitened_factor)
from repro.parallel.runner import Runner
from repro.runtime import spans


class SummaryStore(NamedTuple):
    locals_: LocalSummary     # stacked (M, ...) per-machine summaries
    G: jax.Array              # (M, s, b) whitened low-rank factors:
    #                           Sdot_m = Kss_L G_m G_mᵀ Kss_Lᵀ
    alive: jax.Array          # (M,) bool — machine participation mask
    Kss: jax.Array            # (s, s) prior support covariance
    Kss_L: jax.Array          # (s, s) chol K_SS (static across mutations)
    A_L: jax.Array            # (s, s) chol(I + Σ_alive G_m G_mᵀ): the ALIVE
    #                           Σ-dot-dot whitened by Kss_L (cached,
    #                           maintained by rank-b updates — never refolded)
    ydd: jax.Array            # (s,)   alive Σ_m y-dot^m (cached)


@linalg.f32_matmuls
def _whitened_chol(G: jax.Array, alive: jax.Array | None = None
                   ) -> jax.Array:
    """chol(I + Σ_m G_m G_mᵀ) over the alive machines: Σ-dot-dot =
    K_SS + jitter·I + Σ_m F_m F_mᵀ whitened by Kss_L.

    The jitter is K_SS's own (the one in ``Kss_L``), a mutation-invariant
    prior scale, so the cold factorization and the incrementally-updated
    one factor THE SAME matrix: assimilate/retire then differ from a full
    recompute only by rank-update roundoff (~1e-13 in float64), not by a
    data-dependent jitter drift.
    """
    M, s, b = G.shape
    if alive is not None:
        G = G * alive.astype(G.dtype)[:, None, None]
    G = jnp.moveaxis(G, 0, 1).reshape(s, M * b)
    return jnp.linalg.cholesky(jnp.eye(s, dtype=G.dtype) + G @ G.T)


def _summarize(kfn, params, S, X, y, runner: Runner):
    """Per-machine local summaries + low-rank factors (paper Steps 1-2)."""
    Xb, yb = runner.shard_blocks(X), runner.shard_blocks(y)

    def fn(Xm, ym, params, S):
        Kss_L = linalg.chol(kfn(params, S, S))
        loc, (_, V, C_L, _) = local_summary(kfn, params, S, Kss_L, Xm, ym)
        return loc, whitened_factor(V, C_L)

    with spans.span("online.summarize"):
        return runner.map(fn, (Xb, yb), (params, S))


def _pad_factor(G: jax.Array, b: int) -> jax.Array:
    """Zero-pad the block axis of an (M, s, b') factor to width b. Padded
    columns contribute 0·0ᵀ to G Gᵀ, so the algebra is unchanged — this is
    what lets waves of different block sizes share one stacked store."""
    if G.shape[-1] >= b:
        return G
    return jnp.pad(G, [(0, 0), (0, 0), (0, b - G.shape[-1])])


@linalg.f32_matmuls
def _cold_store(kfn, params, S, locals_: LocalSummary,
                G: jax.Array) -> SummaryStore:
    """Assemble a SummaryStore from freshly-summarized blocks: the ONE
    place the global factor is Cholesky'd from scratch (cold O(|S|³), paid
    once per store lifetime) — shared by the PITC and PIC builders so both
    anchor the same jitter to the same matrix."""
    with spans.span("online.cold_store"):
        alive = jnp.ones((locals_.ydot.shape[0],), bool)
        Kss = kfn(params, S, S)
        ydd = jnp.sum(locals_.ydot, axis=0)
        return SummaryStore(locals_, G, alive, Kss, linalg.chol(Kss),
                            _whitened_chol(G), ydd)


def build(kfn, params, S, X, y, runner: Runner) -> SummaryStore:
    """Initial store from blocked data (paper Steps 1-3)."""
    locals_, G = _summarize(kfn, params, S, X, y, runner)
    return _cold_store(kfn, params, S, locals_, G)


@linalg.f32_matmuls
def global_summary(store: SummaryStore) -> GlobalSummary:
    """Assemble eqs. (5)-(6) from whatever machines are alive — the full
    (non-incremental) reference the cached ``A_L``/``ydd`` are tested
    against; use it for arbitrary alive-mask views (``with_alive``)."""
    w = store.alive.astype(store.locals_.ydot.dtype)
    ydd = jnp.einsum("m,ms->s", w, store.locals_.ydot)
    Sdd = store.Kss + jnp.einsum("m,mst->st", w, store.locals_.Sdot)
    return GlobalSummary(ydd, Sdd)


def to_state(store: SummaryStore, S: jax.Array) -> api.PITCState:
    """Assemble the cached prediction factors (eqs. 7-8 precomputation).

    O(|S|²): ``A_L`` is maintained incrementally by assimilate/retire, so
    only the two triangular solves of the whitened weights
    w = A_L⁻¹ Kss_L⁻¹ ÿ remain here — the |S|³ factorization happens once
    at ``build`` and never again across the store's lifetime."""
    with spans.span("online.to_state"):
        c = linalg.tri_solve(store.Kss_L, store.ydd)
        return api.PITCState(S, store.Kss_L, store.A_L,
                             linalg.tri_solve(store.A_L, c))


def _fold_in(store: SummaryStore, locals_new: LocalSummary,
             G_new: jax.Array) -> SummaryStore:
    """Append new machine blocks and rank-update the cached global factors."""
    with spans.span("online.fold_in"):
        b = max(store.G.shape[-1], G_new.shape[-1])
        merged = LocalSummary(
            jnp.concatenate([store.locals_.ydot, locals_new.ydot]),
            jnp.concatenate([store.locals_.Sdot, locals_new.Sdot]))
        G = jnp.concatenate([_pad_factor(store.G, b), _pad_factor(G_new, b)])
        alive = jnp.concatenate(
            [store.alive, jnp.ones((G_new.shape[0],), bool)])
        # one rank-(M'·b) update: stack the new machines' factor columns
        W = jnp.concatenate([g for g in G_new], axis=1)        # (s, M'·b)
        with spans.span("online.rank_update"):
            A_L = linalg.chol_update_rank(store.A_L, W)
        ydd = store.ydd + jnp.sum(locals_new.ydot, axis=0)
        return SummaryStore(merged, G, alive, store.Kss, store.Kss_L, A_L,
                            ydd)


def assimilate(store: SummaryStore, kfn, params, S, X_new, y_new,
               runner: Runner) -> SummaryStore:
    """Fold a new data stream (D', y_D') in — Sec. 5.2.

    The new blocks are summarized in parallel and appended; old summaries
    are reused untouched, and the global factor is advanced by one
    rank-(M'·b) Cholesky update — O(|S|²·b) per new block, no |S|³
    anywhere."""
    with spans.span("online.assimilate"):
        locals_new, G_new = _summarize(kfn, params, S, X_new, y_new, runner)
        return _fold_in(store, locals_new, G_new)


def retire(store: SummaryStore, machine: int) -> SummaryStore:
    """Drop a machine's contribution (failure or decommission): rank-b
    DOWNdate of the cached factor. No-op if already retired."""
    api.check_machine_index(store.alive.shape[0], machine)
    alive = api.concrete_alive_mask(store.alive)
    if alive is None:
        raise TypeError(
            "retire() branches on the alive mask host-side (the "
            "already-retired no-op check) and cannot run under jit/vmap; "
            "flip machines wholesale with with_alive(store, mask), whose "
            "refold path traces")
    if not alive[machine]:
        return store
    A_L = linalg.chol_update_rank(store.A_L, store.G[machine], sign=-1.0)
    return store._replace(alive=store.alive.at[machine].set(False),
                          A_L=A_L,
                          ydd=store.ydd - store.locals_.ydot[machine])


def revive(store: SummaryStore, machine: int) -> SummaryStore:
    """Fold a previously-retired machine back in (rank-b update)."""
    api.check_machine_index(store.alive.shape[0], machine)
    alive = api.concrete_alive_mask(store.alive)
    if alive is None:
        raise TypeError(
            "revive() branches on the alive mask host-side (the "
            "already-alive no-op check) and cannot run under jit/vmap; "
            "flip machines wholesale with with_alive(store, mask), whose "
            "refold path traces")
    if alive[machine]:
        return store
    A_L = linalg.chol_update_rank(store.A_L, store.G[machine])
    return store._replace(alive=store.alive.at[machine].set(True),
                          A_L=A_L,
                          ydd=store.ydd + store.locals_.ydot[machine])


def with_alive(store: SummaryStore, alive: jax.Array, *,
               mode: str = "auto") -> SummaryStore:
    """Arbitrary alive-mask view (straggler deadlines flip many machines at
    once) — the one sanctioned way to set ``alive`` wholesale (a raw
    ``_replace`` would desynchronize the cache). Two realizations:

    * ``incremental`` — one rank-b cholupdate/downdate of ``A_L`` per
      FLIPPED machine (retire/revive chain): O(|S|²·b·h) for Hamming
      distance h, so a small deadline flip costs O(|S|²·b) — no |S|³
      anywhere;
    * ``refold``      — re-derive ``A_L`` from the alive machines' factors
      in one pass (``_whitened_chol``, the cold-factorization float path):
      the Gram product of the alive factors, O(|S|²·M·b), then an
      O(|S|³/3) factorization.

    ``mode="auto"`` picks by the Hamming distance of the mask: the
    incremental chain while h·b <= |S|/3 + M, the refold past it. The rule
    counts h·b rank-1 sweeps at O(|S|²) each against the refold's
    factorization; it takes the refold's Gram product as cheap next to the
    sweeps, since it is one matmul where each sweep is a chain of |S|
    dependent column steps (the crossover is not timed on a chip). Both
    paths produce the same matrix; they differ only in float path
    (rank-update roundoff ~1e-13 in float64, tests/test_state_store.py).
    """
    alive = jnp.asarray(alive, bool)
    if mode not in ("auto", "incremental", "refold"):
        raise ValueError(f"unknown with_alive mode {mode!r}")
    if isinstance(alive, jax.core.Tracer) or \
            isinstance(store.alive, jax.core.Tracer):
        # under jit/vmap the Hamming distance is data we cannot branch on
        # host-side; the refold is the pure-jnp realization and traces fine
        if mode == "incremental":
            raise ValueError(
                "with_alive(mode='incremental') needs concrete masks (it "
                "dispatches a host-side retire/revive chain); under "
                "jit/vmap use mode='auto'/'refold'")
        mode = "refold"
    if mode != "refold":
        flips = np.flatnonzero(np.asarray(store.alive) != np.asarray(alive))
        if mode == "auto":
            s = store.A_L.shape[0]
            b = store.G.shape[-1]
            M = store.alive.shape[0]
            mode = ("incremental" if len(flips) * b <= s // 3 + M
                    else "refold")
    if mode == "incremental":
        for m in flips:
            m = int(m)
            store = revive(store, m) if bool(alive[m]) else retire(store, m)
        return store
    store = store._replace(alive=alive)
    return store._replace(A_L=_whitened_chol(store.G, alive),
                          ydd=global_summary(store).ydd)


@linalg.f32_matmuls
def replace_block(store: SummaryStore, kfn, params, S, machine: int,
                  Xm, ym) -> SummaryStore:
    """Recompute ONE machine's summary from its (re-read) data shard and
    fold it in alive — the fault-recovery reassign path. Incremental: at
    most one downdate (if the stale summary was still folded in) plus one
    update."""
    api.check_machine_index(store.alive.shape[0], machine)
    store = retire(store, machine)
    loc, (_, V, C_L, _) = local_summary(kfn, params, S, store.Kss_L, Xm, ym)
    G_m = whitened_factor(V, C_L)
    b = max(store.G.shape[-1], G_m.shape[-1])
    G_m = _pad_factor(G_m[None], b)[0]
    locs = LocalSummary(store.locals_.ydot.at[machine].set(loc.ydot),
                        store.locals_.Sdot.at[machine].set(loc.Sdot))
    store = store._replace(locals_=locs, G=_pad_factor(store.G, b)
                           .at[machine].set(G_m))
    return revive(store, machine)


def predict_ppitc(store: SummaryStore, kfn, params, S, U) -> tuple:
    """pPITC prediction (eqs. 7-8) straight from the store: thin wrapper
    over ``to_state`` + ``ppitc.predict_batch``."""
    post = predict_batch(kfn, params, to_state(store, S), U)
    return post.mean, post.cov


# ---------------------------------------------------------------------------
# Method-owned StateStore implementations (api.StateStore protocol).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PITCStore:
    """pPITC's ``api.StateStore``: owns the fit context, emits PITCState.

    Immutable — every mutation returns a new store sharing the untouched
    leaves, so serving can keep the previous store alive until a hot-swap
    commits (launch/gp_serve.py).
    """
    kfn: object
    params: dict
    S: jax.Array
    runner: Runner
    store: SummaryStore

    # -- protocol -----------------------------------------------------------

    def assimilate(self, X_new, y_new, runner: Runner | None = None
                   ) -> "PITCStore":
        """Fold a new stream in. ``runner`` overrides how the WAVE is
        blocked (elastic scale-up arrives on however many machines it
        arrives on); defaults to the fit-time runner."""
        return dataclasses.replace(self, store=assimilate(
            self.store, self.kfn, self.params, self.S, X_new, y_new,
            runner or self.runner))

    def retire(self, machine: int) -> "PITCStore":
        new = retire(self.store, machine)
        return self if new is self.store else \
            dataclasses.replace(self, store=new)

    def revive(self, machine: int) -> "PITCStore":
        new = revive(self.store, machine)
        return self if new is self.store else \
            dataclasses.replace(self, store=new)

    def to_state(self) -> api.PITCState:
        return to_state(self.store, self.S)

    # -- beyond-protocol surface (fault/straggler runtimes) -----------------

    @property
    def alive(self) -> jax.Array:
        return self.store.alive

    @property
    def num_machines(self) -> int:
        return int(self.store.alive.shape[0])

    def with_alive(self, alive, *, mode: str = "auto") -> "PITCStore":
        return dataclasses.replace(self, store=with_alive(self.store, alive,
                                                          mode=mode))

    def reassign(self, machine: int, Xm, ym) -> "PITCStore":
        return dataclasses.replace(self, store=replace_block(
            self.store, self.kfn, self.params, self.S, machine, Xm, ym))

    def global_summary(self) -> GlobalSummary:
        return global_summary(self.store)

    def predict(self, U) -> tuple:
        """(mean, cov) over U from the current alive set."""
        return predict_ppitc(self.store, self.kfn, self.params, self.S, U)


def init_pitc_store(kfn, params, X, y, *, S, runner: Runner) -> PITCStore:
    """``GPMethod.init_store`` for ppitc/pitc (registered in core/ppitc.py)."""
    return PITCStore(kfn, params, S, runner,
                     build(kfn, params, S, X, y, runner))


class PICBlocks(NamedTuple):
    """Per-block caches for the pPIC local correction (eqs. 12-14); the
    global algebra lives in the shared SummaryStore. Leading axis M."""
    Xb: jax.Array      # (M, b, d)
    yb: jax.Array      # (M, b)
    V: jax.Array       # (M, s, b) Kss_L⁻¹ K_SDm
    C_L: jax.Array     # (M, b, b)
    Wy: jax.Array      # (M, b)


def _summarize_pic(kfn, params, S, X, y, runner: Runner):
    """Per-machine summaries + the eqs. (12)-(14) caches, one map."""
    Xb, yb = runner.shard_blocks(X), runner.shard_blocks(y)

    def fn(Xm, ym, params, S):
        Kss_L = linalg.chol(kfn(params, S, S))
        loc, (_, V, C_L, Wy) = local_summary(kfn, params, S, Kss_L, Xm, ym)
        return loc, whitened_factor(V, C_L), V, C_L, Wy

    loc, G, V, C_L, Wy = runner.map(fn, (Xb, yb), (params, S))
    return loc, G, PICBlocks(Xb, yb, V, C_L, Wy)


@dataclasses.dataclass(frozen=True)
class PICStore:
    """pPIC's ``api.StateStore``: the PITC global algebra + per-block local
    caches; ``to_state`` emits an ``api.PICState`` over the ALIVE blocks
    with refreshed centroids, so ``GPServer(routed=True)`` hot-swaps
    streamed data (Remark 2 keeps holding: routing targets are exactly the
    blocks that can serve a local correction).

    Streamed waves must keep the fit-time block size (|D'|/M' == b): the
    block caches are stacked arrays, and zero-padding *data* rows would
    inject spurious noise-only observations into Σ_{DmDm|S} (see
    Runner.shard_blocks). Retiring a machine shrinks the state's block axis
    at the next ``to_state`` — one serving recompile, flagged by gp_serve.
    """
    kfn: object
    params: dict
    S: jax.Array
    runner: Runner
    store: SummaryStore
    blocks: PICBlocks

    @property
    def block_size(self) -> int:
        return int(self.blocks.Xb.shape[1])

    def assimilate(self, X_new, y_new, runner: Runner | None = None
                   ) -> "PICStore":
        runner = runner or self.runner
        M_new = runner.num_machines
        b_new = X_new.shape[0] // M_new
        if X_new.shape[0] % M_new or b_new != self.block_size:
            raise ValueError(
                f"pPIC streaming keeps the fit-time block size: got "
                f"|D'|={X_new.shape[0]} over M={M_new} machines "
                f"(b={X_new.shape[0] / M_new:g}) but the store's blocks are "
                f"b={self.block_size}. Re-chunk the wave (or use the pPITC "
                f"store, which accepts any block size).")
        loc, G, blocks_new = _summarize_pic(self.kfn, self.params, self.S,
                                            X_new, y_new, runner)
        merged = PICBlocks(*(jnp.concatenate([a, b]) for a, b in
                             zip(self.blocks, blocks_new)))
        return dataclasses.replace(
            self, store=_fold_in(self.store, loc, G), blocks=merged)

    def retire(self, machine: int) -> "PICStore":
        new = retire(self.store, machine)
        return self if new is self.store else \
            dataclasses.replace(self, store=new)

    def revive(self, machine: int) -> "PICStore":
        new = revive(self.store, machine)
        return self if new is self.store else \
            dataclasses.replace(self, store=new)

    def to_state(self) -> api.PICState:
        st = self.store
        glob = to_state(st, self.S)      # shared O(|S|²) global-factor path
        if isinstance(st.alive, jax.core.Tracer):
            # under jit/vmap the mask is data we cannot branch on, and the
            # dead-block gather below is a data-dependent shape anyway. A
            # traced store can only have been built inside the trace
            # (retire/revive/with_alive-incremental are host-side), so all
            # blocks are alive by construction — take the no-gather path.
            all_alive = True
        else:
            all_alive = bool(np.asarray(st.alive).all())
        if all_alive:
            # streaming common case: no gather — every block cache (incl.
            # the full Xb dataset) is passed through by reference, keeping
            # update() at the advertised O(|S|² b)
            blk = self.blocks
        else:
            idx = jnp.asarray(np.flatnonzero(np.asarray(st.alive)))
            blk = PICBlocks(*(a[idx] for a in self.blocks))
        return api.PICState(
            self.S, glob.Kss_L, glob.A_L, glob.w, blk.Xb, blk.yb,
            blk.V, blk.C_L, blk.Wy, clustering.block_centroids(blk.Xb))


def init_pic_store(kfn, params, X, y, *, S, runner: Runner) -> PICStore:
    """``GPMethod.init_store`` for ppic/pic (registered in core/ppic.py)."""
    loc, G, blocks = _summarize_pic(kfn, params, S, X, y, runner)
    return PICStore(kfn, params, S, runner,
                    _cold_store(kfn, params, S, loc, G), blocks)
