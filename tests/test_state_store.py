"""Incremental-state API (api.StateStore): rank-b Cholesky updates,
store lifecycle (assimilate / retire / revive / to_state), streamed routed
serving, versioned state checkpointing, and the GPServer streaming surface.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (api, covariance as cov, gp, hyper, linalg, online,
                        picf, pitc, ppic, ppitc, serialize)
from repro.launch.gp_serve import GPServer
from repro.parallel.runner import VmapRunner

from helpers import make_problem


@pytest.fixture(scope="module")
def prob():
    return make_problem()


@pytest.fixture(scope="module")
def runner(prob):
    return VmapRunner(M=prob["M"])


def _tree_equal(a, b):
    return all(bool(jnp.array_equal(x, y)) and x.dtype == y.dtype
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# linalg: rank-1 / rank-b Cholesky update and downdate
# ---------------------------------------------------------------------------

class TestCholUpdate:
    def _psd(self, n, seed=0):
        A0 = np.random.RandomState(seed).randn(n, 2 * n)
        return jnp.asarray(A0 @ A0.T + n * np.eye(n))

    def test_rank1_update_matches_refactorization(self):
        A = self._psd(16)
        L = jnp.linalg.cholesky(A)
        w = jnp.asarray(np.random.RandomState(1).randn(16))
        ref = jnp.linalg.cholesky(A + jnp.outer(w, w))
        np.testing.assert_allclose(linalg.cholupdate(L, w), ref, atol=1e-12)

    def test_rank1_downdate_inverts_update(self):
        A = self._psd(16)
        L = jnp.linalg.cholesky(A)
        w = jnp.asarray(np.random.RandomState(2).randn(16))
        np.testing.assert_allclose(
            linalg.choldowndate(linalg.cholupdate(L, w), w), L, atol=1e-12)

    def test_rank_b_update_matches_refactorization(self):
        A = self._psd(20)
        L = jnp.linalg.cholesky(A)
        W = jnp.asarray(np.random.RandomState(3).randn(20, 7))
        ref = jnp.linalg.cholesky(A + W @ W.T)
        np.testing.assert_allclose(linalg.chol_update_rank(L, W), ref,
                                   atol=1e-11)
        np.testing.assert_allclose(
            linalg.chol_update_rank(ref, W, sign=-1.0), L, atol=1e-11)

    def test_zero_columns_are_inert(self):
        """Zero update vectors (the factor-padding convention) are no-ops."""
        A = self._psd(10)
        L = jnp.linalg.cholesky(A)
        W = jnp.zeros((10, 4), L.dtype)
        np.testing.assert_allclose(linalg.chol_update_rank(L, W), L, atol=0)


# ---------------------------------------------------------------------------
# Acceptance: incremental to_state (cholupdate path) vs full recomputation
# ---------------------------------------------------------------------------

class TestIncrementalToState:
    def test_assimilate_matches_full_recompute_1e5(self, prob, runner):
        """float64 gate from the issue: after streaming waves through the
        rank-b update path, (A_L, w) match a from-scratch O(|S|^3)
        recomputation of the same summaries to 1e-5 (observed ~1e-12)."""
        p = prob
        n1 = p["X"].shape[0] // 2
        store = api.init_store("ppitc", p["kfn"], p["params"], p["X"][:n1],
                               p["y"][:n1], S=p["S"], runner=runner)
        store = store.assimilate(p["X"][n1:], p["y"][n1:])
        # full recompute of the SAME summaries (alive-mask refold)
        ref = online.with_alive(store.store, store.store.alive,
                                mode="refold")
        np.testing.assert_allclose(store.store.A_L, ref.A_L, atol=1e-5)
        st_inc = store.to_state()
        st_ref = online.to_state(ref, p["S"])
        np.testing.assert_allclose(st_inc.w, st_ref.w, atol=1e-5)
        # and both match a genuinely cold fit of the concatenated data
        cold = ppitc.fit(p["kfn"], p["params"], p["X"], p["y"], S=p["S"],
                         runner=VmapRunner(M=2 * p["M"]))
        np.testing.assert_allclose(st_inc.A_L, cold.A_L, atol=1e-5)
        np.testing.assert_allclose(st_inc.w, cold.w, atol=1e-5)

    def test_retire_downdate_matches_survivor_refold(self, prob, runner):
        p = prob
        store = api.init_store("ppitc", p["kfn"], p["params"], p["X"],
                               p["y"], S=p["S"], runner=runner).retire(1)
        ref = online.with_alive(store.store, store.store.alive,
                                mode="refold")
        np.testing.assert_allclose(store.store.A_L, ref.A_L, atol=1e-5)

    def test_to_state_has_no_cubic_refactorization(self, prob, runner):
        """Structural check of the O(|S|^2) claim: to_state after retire
        reuses the cached (downdated) factor — it does NOT equal a chol of
        the alive Sdd bit-for-bit, it equals the downdate of the cold
        (whitened) factor (same matrix, different float path)."""
        p = prob
        store = api.init_store("ppitc", p["kfn"], p["params"], p["X"],
                               p["y"], S=p["S"], runner=runner)
        expected = linalg.chol_update_rank(store.store.A_L,
                                           store.store.G[2], sign=-1.0)
        np.testing.assert_array_equal(store.retire(2).to_state().A_L,
                                      expected)


# ---------------------------------------------------------------------------
# Store lifecycle (issue satellite): retire -> revive -> to_state roundtrip,
# assimilate-then-checkpoint == recompute-from-scratch
# ---------------------------------------------------------------------------

class TestWithAliveHamming:
    """``online.with_alive`` picks rank-b cholupdate/downdate vs full refold
    by the Hamming distance of the alive mask (ISSUE satellite): small
    deadline flips are O(|S|²·b) retire/revive chains, wholesale flips take
    the one-pass O(|S|³) refold. Both must produce the same matrix."""

    def _store(self, prob, runner):
        # M=12 -> b=8 < |S|: the regime where rank-b updates beat the
        # refold (the fixture's b=24 > |S|=12 would always refold — for
        # blocks wider than the support set, re-factorizing |S|³/3 is
        # genuinely cheaper than b rank-1 sweeps)
        del runner
        return api.init_store("ppitc", prob["kfn"], prob["params"],
                              prob["X"], prob["y"], S=prob["S"],
                              runner=VmapRunner(M=12))

    def test_small_flip_is_incremental(self, prob, runner):
        """A single-machine flip must follow the retire float path exactly
        (bitwise): the incremental branch IS a retire chain."""
        store = self._store(prob, runner)
        mask = np.asarray(store.alive).copy()
        mask[1] = False
        flipped = store.with_alive(jnp.asarray(mask))
        np.testing.assert_array_equal(flipped.store.A_L,
                                      store.retire(1).store.A_L)

    def test_incremental_matches_refold(self, prob, runner):
        store = self._store(prob, runner)
        mask = np.asarray(store.alive).copy()
        mask[0] = mask[3] = False
        inc = online.with_alive(store.store, jnp.asarray(mask),
                                mode="incremental")
        ref = online.with_alive(store.store, jnp.asarray(mask),
                                mode="refold")
        np.testing.assert_array_equal(inc.alive, ref.alive)
        np.testing.assert_allclose(inc.A_L, ref.A_L, atol=1e-10)
        np.testing.assert_allclose(inc.ydd, ref.ydd, atol=1e-10)

    def test_wholesale_flip_refolds(self, prob, runner):
        """Flipping every machine exceeds the h·b crossover: auto must take
        the refold float path (bitwise equal to mode='refold')."""
        store = self._store(prob, runner)
        mask = ~np.asarray(store.alive)
        mask[0] = True                      # keep one machine alive
        auto = online.with_alive(store.store, jnp.asarray(mask))
        ref = online.with_alive(store.store, jnp.asarray(mask),
                                mode="refold")
        np.testing.assert_array_equal(auto.A_L, ref.A_L)

    def test_noop_mask_returns_store_unchanged(self, prob, runner):
        store = self._store(prob, runner)
        same = online.with_alive(store.store, store.store.alive)
        np.testing.assert_array_equal(same.A_L, store.store.A_L)

    def test_bad_mode_rejected(self, prob, runner):
        store = self._store(prob, runner)
        with pytest.raises(ValueError, match="with_alive mode"):
            online.with_alive(store.store, store.store.alive, mode="nope")

    def test_traceable_under_jit(self, prob, runner):
        """A traced mask cannot drive the host-side Hamming dispatch:
        'auto' must fall back to the pure-jnp refold (and still be right);
        forcing 'incremental' under trace is an explicit error."""
        store = self._store(prob, runner)
        mask = store.store.alive.at[1].set(False)
        jit_ydd = jax.jit(
            lambda m: online.with_alive(store.store, m).ydd)(mask)
        ref = online.with_alive(store.store, mask, mode="refold")
        np.testing.assert_allclose(jit_ydd, ref.ydd, atol=1e-12)
        with pytest.raises(ValueError, match="concrete masks"):
            jax.jit(lambda m: online.with_alive(
                store.store, m, mode="incremental").ydd)(mask)


class TestStoreLifecycle:
    def test_protocol_membership(self, prob, runner):
        for name, kw in (("ppitc", dict(S=prob["S"], runner=runner)),
                         ("ppic", dict(S=prob["S"], runner=runner)),
                         ("picf", dict(rank=48, runner=runner)),
                         ("pitc", dict(S=prob["S"], M=prob["M"])),
                         ("pic", dict(S=prob["S"], M=prob["M"]))):
            store = api.init_store(name, prob["kfn"], prob["params"],
                                   prob["X"], prob["y"], **kw)
            assert isinstance(store, api.StateStore), name

    def test_fgp_has_no_store(self, prob):
        with pytest.raises(ValueError, match="no incremental StateStore"):
            api.init_store("fgp", prob["kfn"], prob["params"], prob["X"],
                           prob["y"])

    @pytest.mark.parametrize("name,kw", [
        ("ppitc", {}), ("ppic", {}), ("picf", {"rank": 48})])
    def test_retire_revive_to_state_roundtrip(self, prob, runner, name, kw):
        """retire -> revive -> to_state reproduces the original state for
        every store-backed method (downdate/update cancel)."""
        kwargs = dict(S=prob["S"], runner=runner) if "rank" not in kw \
            else dict(runner=runner, **kw)
        store = api.init_store(name, prob["kfn"], prob["params"], prob["X"],
                               prob["y"], **kwargs)
        s0 = store.to_state()
        s1 = store.retire(2).revive(2).to_state()
        for f, a, b in zip(s0._fields, s0, s1):
            np.testing.assert_allclose(a, b, atol=1e-10,
                                       err_msg=f"{name}.{f}")

    def test_retire_is_idempotent_and_revive_noop_when_alive(self, prob,
                                                             runner):
        store = api.init_store("ppitc", prob["kfn"], prob["params"],
                               prob["X"], prob["y"], S=prob["S"],
                               runner=runner)
        assert store.revive(1) is store           # already alive
        dead = store.retire(1)
        assert dead.retire(1) is dead             # already retired

    @pytest.mark.parametrize("name,kw", [
        ("ppitc", {}), ("picf", {"rank": 48})])
    def test_out_of_range_machine_rejected(self, prob, runner, name, kw):
        """jnp drops OOB scatter updates while clamping OOB gathers, so an
        unchecked bad id would corrupt the cached factor silently; the
        stores must raise instead."""
        kwargs = dict(S=prob["S"], runner=runner) if "rank" not in kw \
            else dict(runner=runner, **kw)
        store = api.init_store(name, prob["kfn"], prob["params"], prob["X"],
                               prob["y"], **kwargs)
        for machine in (prob["M"], -1, 10 ** 6):
            with pytest.raises(IndexError, match="out of range"):
                store.retire(machine)
            with pytest.raises(IndexError, match="out of range"):
                store.revive(machine)

    def test_all_alive_to_state_shares_block_buffers(self, prob, runner):
        """The streaming common case (nothing retired) must not copy the
        per-block caches — Xb in the emitted state IS the store's buffer."""
        store = api.init_store("ppic", prob["kfn"], prob["params"],
                               prob["X"], prob["y"], S=prob["S"],
                               runner=runner)
        assert store.to_state().Xb is store.blocks.Xb
        picf_store = api.init_store("picf", prob["kfn"], prob["params"],
                                    prob["X"], prob["y"], rank=48,
                                    runner=runner)
        assert picf_store.to_state().Xb is picf_store.Xb

    @pytest.mark.parametrize("name", ["ppitc", "ppic"])
    def test_assimilate_then_checkpoint_equals_recompute(self, prob, runner,
                                                         name, tmp_path):
        """Stream half the data in, checkpoint the state, reload: equals a
        cold fit of the concatenated data (and the reload is bitwise)."""
        p = prob
        n1 = p["X"].shape[0] // 2
        store = api.init_store(name, p["kfn"], p["params"], p["X"][:n1],
                               p["y"][:n1], S=p["S"], runner=runner)
        store = store.assimilate(p["X"][n1:], p["y"][n1:])
        state = store.to_state()
        path = tmp_path / f"{name}.npz"
        serialize.save_state(path, state)
        loaded = serialize.load_state(path)
        assert _tree_equal(state, loaded)
        cold = api.get(name).fit(p["kfn"], p["params"], p["X"], p["y"],
                                 S=p["S"], runner=VmapRunner(M=2 * p["M"]))
        for f, a, b in zip(state._fields, loaded, cold):
            np.testing.assert_allclose(a, b, atol=1e-9,
                                       err_msg=f"{name}.{f}")

    def test_pic_centroids_refresh_on_stream_and_retire(self, prob, runner):
        p = prob
        n1 = p["X"].shape[0] // 2
        store = api.init_store("ppic", p["kfn"], p["params"], p["X"][:n1],
                               p["y"][:n1], S=p["S"], runner=runner)
        M0 = store.to_state().centroids.shape[0]
        grown = store.assimilate(p["X"][n1:], p["y"][n1:])
        assert grown.to_state().centroids.shape[0] == 2 * M0
        shrunk = grown.retire(0).to_state()
        assert shrunk.centroids.shape[0] == 2 * M0 - 1
        np.testing.assert_allclose(shrunk.centroids,
                                   jnp.mean(shrunk.Xb, axis=1), atol=0)

    def test_pic_wave_block_size_enforced(self, prob, runner):
        p = prob
        store = api.init_store("ppic", p["kfn"], p["params"], p["X"],
                               p["y"], S=p["S"], runner=runner)
        with pytest.raises(ValueError, match="block size"):
            store.assimilate(p["X"][: p["X"].shape[0] // 2],
                             p["y"][: p["X"].shape[0] // 2])

    def test_pitc_waves_of_any_block_size(self, prob, runner):
        """pPITC summaries are block-size-agnostic: a wave with a different
        b pads the factor store and still matches the per-wave cold sum."""
        p = prob
        store = api.init_store("ppitc", p["kfn"], p["params"], p["X"],
                               p["y"], S=p["S"], runner=runner)
        X2 = jax.random.normal(jax.random.PRNGKey(5), (6, 3), jnp.float64)
        y2 = jnp.sin(X2[:, 0])
        grown = store.assimilate(X2, y2, runner=VmapRunner(M=2))   # b=3
        ref = online.with_alive(grown.store, grown.store.alive,
                                mode="refold")
        np.testing.assert_allclose(grown.store.A_L, ref.A_L, atol=1e-10)


# ---------------------------------------------------------------------------
# pICF row-append / retire on the distributed factor
# ---------------------------------------------------------------------------

class TestPICFStore:
    def test_append_extends_factor_in_pivot_basis(self, prob, runner):
        """Streamed factor columns are the forward solve Lp f = k(P, x) —
        and the incremental Phi_L matches a full refactorization of the
        extended factor to 1e-5 (float64 gate)."""
        p = prob
        store = api.init_store("picf", p["kfn"], p["params"], p["X"],
                               p["y"], rank=48, runner=runner)
        X2 = jax.random.normal(jax.random.PRNGKey(7),
                               p["X"].shape, jnp.float64)
        y2 = jnp.cos(X2[:, 0])
        grown = store.assimilate(X2, y2)
        # Nyström-extension identity on the appended blocks
        Xb2 = runner.shard_blocks(X2)
        F_ref = jax.vmap(lambda Xm: linalg.tri_solve(
            store.Lp, p["kfn"](p["params"], store.Xp, Xm)))(Xb2)
        np.testing.assert_array_equal(grown.F[p["M"]:], F_ref)
        # incremental Phi_L vs refactorization of I + sum F F^T / s2
        s2 = cov.noise_var(p["params"])
        R = store.Phi_L.shape[0]
        Phi = jnp.eye(R, dtype=jnp.float64) + jnp.sum(
            jnp.einsum("mrb,msb->mrs", grown.F, grown.F), 0) / s2
        np.testing.assert_allclose(grown.Phi_L, jnp.linalg.cholesky(Phi),
                                   atol=1e-5)

    def test_retire_appended_restores_original(self, prob, runner):
        p = prob
        store = api.init_store("picf", p["kfn"], p["params"], p["X"],
                               p["y"], rank=48, runner=runner)
        X2 = jax.random.normal(jax.random.PRNGKey(8),
                               p["X"].shape, jnp.float64)
        grown = store.assimilate(X2, jnp.sin(X2[:, 1]))
        for m in range(p["M"], 2 * p["M"]):
            grown = grown.retire(m)
        s0, s1 = store.to_state(), grown.to_state()
        np.testing.assert_allclose(s1.Phi_L, s0.Phi_L, atol=1e-10)
        np.testing.assert_allclose(s1.ydd, s0.ydd, atol=1e-10)
        np.testing.assert_array_equal(s1.Xb, s0.Xb)

    def test_streamed_predictions_finite_and_consistent(self, prob, runner):
        p = prob
        store = api.init_store("picf", p["kfn"], p["params"], p["X"],
                               p["y"], rank=48, runner=runner)
        half = p["X"].shape[0] // 2
        # stream a slice of the SAME data distribution back in
        grown = store.assimilate(
            p["X"] + 0.01 * jax.random.normal(jax.random.PRNGKey(9),
                                              p["X"].shape, jnp.float64),
            p["y"])
        mean, var = picf.predict_batch_diag(p["kfn"], p["params"],
                                            grown.to_state(), p["U"])
        assert bool(jnp.isfinite(mean).all()) and bool(
            jnp.isfinite(var).all())
        assert half > 0

    def test_wave_block_size_enforced(self, prob, runner):
        p = prob
        store = api.init_store("picf", p["kfn"], p["params"], p["X"],
                               p["y"], rank=48, runner=runner)
        with pytest.raises(ValueError, match="block size"):
            store.assimilate(p["X"][:12], p["y"][:12])


# ---------------------------------------------------------------------------
# Acceptance: streamed PICState through GPServer(routed=True) == cold pPIC
# fit on the concatenated data (property-tested over wave splits)
# ---------------------------------------------------------------------------

class TestStreamedRoutedServing:
    @settings(max_examples=8, deadline=None)
    @given(k=st.integers(min_value=1, max_value=3), seed=st.integers(0, 99))
    def test_streamed_equals_cold_fit_routed(self, prob, k, seed):
        """Any split of the blocks into (first wave, second wave) and any
        query batch: the streamed PICState served routed equals the cold
        pPIC fit of the concatenated data served routed."""
        p = prob
        b = p["X"].shape[0] // p["M"]          # fit-time block size
        n1 = k * b
        store = api.init_store("ppic", p["kfn"], p["params"], p["X"][:n1],
                               p["y"][:n1], S=p["S"], runner=VmapRunner(M=k))
        store = store.assimilate(p["X"][n1:], p["y"][n1:],
                                 runner=VmapRunner(M=p["M"] - k))
        streamed = api.FittedGP(api.get("ppic"), p["kfn"], p["params"],
                                store.to_state())
        cold = api.fit("ppic", p["kfn"], p["params"], p["X"], p["y"],
                       S=p["S"], runner=VmapRunner(M=p["M"]))
        perm = np.random.RandomState(seed).permutation(p["U"].shape[0])
        U = p["U"][jnp.asarray(perm)]
        srv = GPServer(streamed, max_batch=8, routed=True)
        m_s, v_s = srv.predict(U)
        m_c, v_c = cold.predict_routed_diag(U)
        np.testing.assert_allclose(m_s, m_c, atol=1e-9)
        np.testing.assert_allclose(v_s, v_c, atol=1e-9)

    def test_update_hot_swaps_routed_server(self, prob, runner):
        """GPServer.update on a routed server: streamed data changes the
        served posterior to the cold-fit-on-all-data one."""
        p = prob
        n1 = p["X"].shape[0] // 2
        store = api.init_store("ppic", p["kfn"], p["params"], p["X"][:n1],
                               p["y"][:n1], S=p["S"],
                               runner=VmapRunner(M=p["M"] // 2))
        srv = GPServer(api.FittedGP(api.get("ppic"), p["kfn"], p["params"],
                                    store.to_state()),
                       max_batch=8, routed=True, store=store)
        m_before, _ = srv.predict(p["U"][:8])
        srv.update(p["X"][n1:], p["y"][n1:])
        m_after, v_after = srv.predict(p["U"][:8])
        cold = api.fit("ppic", p["kfn"], p["params"], p["X"], p["y"],
                       S=p["S"], runner=runner)
        ref_m, ref_v = cold.predict_routed_diag(p["U"][:8])
        np.testing.assert_allclose(m_after, ref_m, atol=1e-9)
        np.testing.assert_allclose(v_after, ref_v, atol=1e-9)
        assert float(jnp.abs(m_after - m_before).max()) > 1e-6
        assert srv.stats.n_updates == 1

    def test_retire_machine_serves_survivors(self, prob, runner):
        p = prob
        store = api.init_store("ppitc", p["kfn"], p["params"], p["X"],
                               p["y"], S=p["S"], runner=runner)
        srv = GPServer(api.FittedGP(api.get("ppitc"), p["kfn"], p["params"],
                                    store.to_state()),
                       max_batch=8, store=store)
        srv.retire_machine(1)
        m, _ = srv.predict(p["U"][:8])
        b = p["X"].shape[0] // p["M"]
        keep = jnp.concatenate([jnp.arange(0, b),
                                jnp.arange(2 * b, p["X"].shape[0])])
        surv = ppitc.fit(p["kfn"], p["params"], p["X"][keep], p["y"][keep],
                         S=p["S"], runner=VmapRunner(M=p["M"] - 1))
        ref, _ = ppitc.predict_batch_diag(p["kfn"], p["params"], surv,
                                          p["U"][:8])
        np.testing.assert_allclose(m, ref, atol=1e-9)
        srv.revive_machine(1)
        assert srv.stats.n_updates == 2

    def test_update_without_store_raises(self, prob, runner):
        model = api.fit("ppitc", prob["kfn"], prob["params"], prob["X"],
                        prob["y"], S=prob["S"], runner=runner)
        srv = GPServer(model, max_batch=8)
        with pytest.raises(ValueError, match="StateStore"):
            srv.update(prob["X"], prob["y"])

    def test_rejected_update_is_atomic(self, prob, runner):
        """A routed server given a centroid-less (pPITC) store must reject
        update() WITHOUT committing the store mutation — a retry through
        the proper path must not fold the wave in twice."""
        p = prob
        pitc_store = api.init_store("ppitc", p["kfn"], p["params"], p["X"],
                                    p["y"], S=p["S"], runner=runner)
        pic_model = api.fit("ppic", p["kfn"], p["params"], p["X"], p["y"],
                            S=p["S"], runner=runner)
        srv = GPServer(pic_model, max_batch=8, routed=True, store=pitc_store)
        m0, _ = srv.predict(p["U"][:4])
        with pytest.raises(ValueError, match="centroids"):
            srv.update(p["X"], p["y"])
        assert srv.store is pitc_store            # store not committed
        assert srv.stats.n_updates == 0
        m1, _ = srv.predict(p["U"][:4])
        np.testing.assert_array_equal(m0, m1)     # posterior untouched


# ---------------------------------------------------------------------------
# Acceptance: save_state / load_state round-trips every registered state
# bitwise
# ---------------------------------------------------------------------------

class TestSerialize:
    def _states(self, p, runner):
        return {
            "FGPState": gp.fit(p["kfn"], p["params"], p["X"], p["y"]),
            "PITCState": ppitc.fit(p["kfn"], p["params"], p["X"], p["y"],
                                   S=p["S"], runner=runner),
            "PICState": ppic.fit(p["kfn"], p["params"], p["X"], p["y"],
                                 S=p["S"], runner=runner),
            "PICFState": picf.fit(p["kfn"], p["params"], p["X"], p["y"],
                                  rank=48, runner=runner),
        }

    def test_every_registered_state_roundtrips_bitwise(self, prob, runner,
                                                       tmp_path):
        states = self._states(prob, runner)
        assert set(states) == set(serialize.STATE_TYPES)
        for name, state in states.items():
            path = serialize.save_state(tmp_path / f"{name}.npz", state)
            loaded = serialize.load_state(path)
            assert type(loaded).__name__ == name
            assert _tree_equal(state, loaded), name
            meta = serialize.peek(path)
            assert meta["state"] == name
            assert meta["schema"] == serialize.SCHEMA_VERSION
            assert set(meta["fields"]) == set(state._fields)

    def test_unregistered_type_rejected(self, tmp_path):
        from repro.core.ppitc import GlobalSummary
        bogus = GlobalSummary(jnp.zeros(2), jnp.eye(2))
        with pytest.raises(ValueError, match="unregistered"):
            serialize.save_state(tmp_path / "x.npz", bogus)

    def test_not_a_checkpoint_rejected(self, tmp_path):
        path = tmp_path / "junk.npz"
        np.savez(open(path, "wb"), a=np.zeros(3))
        with pytest.raises(ValueError, match="not a repro state"):
            serialize.load_state(path)

    def test_field_drift_rejected(self, prob, runner, tmp_path):
        """A checkpoint whose fields no longer match the state class must
        fail loudly, not mis-assemble."""
        state = ppitc.fit(prob["kfn"], prob["params"], prob["X"], prob["y"],
                          S=prob["S"], runner=runner)
        path = serialize.save_state(tmp_path / "s.npz", state)
        with np.load(path) as z:
            payload = {k: z[k] for k in z.files if k != "field:w"}
        np.savez(open(path, "wb"), **payload)
        with pytest.raises(ValueError, match="field mismatch"):
            serialize.load_state(path)

    @pytest.mark.parametrize("name", ["FGPState", "PITCState", "PICState",
                                      "PICFState"])
    def test_v1_state_checkpoint_loads(self, prob, runner, tmp_path, name):
        """Schema-v1 checkpoints still load: the pPITC/pPIC layouts (an
        unwhitened Sdd_L and alpha = Sdd⁻¹ÿ; pPIC's Ksd/ydot/beta/B/Sdot
        block caches) convert to the whitened v2 state, the unchanged
        types load as they are."""
        state = self._states(prob, runner)[name]
        fields = {f: np.asarray(v) for f, v in zip(state._fields, state)}
        if name in ("PITCState", "PICState"):
            L, A_L = fields.pop("Kss_L"), fields.pop("A_L")
            fields["Kss_L"], fields["Sdd_L"] = L, L @ A_L
            fields["alpha"] = np.linalg.solve(
                (L @ A_L).T, fields.pop("w"))       # Sdd⁻¹ÿ = L⁻ᵀA_L⁻ᵀw
        if name == "PICState":
            V = fields.pop("V")
            M, s_, _ = V.shape
            fields["Ksd"] = np.einsum("st,mtb->msb", L, V)
            fields.update(ydot=np.zeros((M, s_)), beta=np.zeros((M, s_)),
                          B=np.zeros((M, s_, s_)), Sdot=np.zeros((M, s_, s_)))
        path = tmp_path / f"{name}_v1.npz"
        with open(path, "wb") as fh:
            np.savez(fh, __schema__=np.int64(1), __state__=np.str_(name),
                     **{"field:" + f: v for f, v in fields.items()})
        loaded = serialize.load_state(path)
        assert type(loaded) is type(state)
        for f, a, b in zip(state._fields, state, loaded):
            np.testing.assert_allclose(b, a, atol=1e-9, err_msg=f)

    @pytest.mark.parametrize("method", ["ppitc", "ppic"])
    def test_v1_store_checkpoint_loads(self, prob, runner, tmp_path, method):
        """Schema-v1 store checkpoints (unwhitened F and Sdd_L; pPIC's
        Ksd/beta/B block caches) convert to the whitened v2 store and keep
        serving the same posterior."""
        p = prob
        store = api.init_store(method, p["kfn"], p["params"], p["X"],
                               p["y"], S=p["S"], runner=runner)
        path = serialize.save_store(tmp_path / "v2.npz", store)
        with np.load(path) as z:
            a = {k: z[k] for k in z.files if k != "__checksums__"}
        L = a["sum:Kss_L"]
        a["sum:F"] = np.einsum("st,mtb->msb", L, a.pop("sum:G"))
        a["sum:Sdd_L"] = L @ a.pop("sum:A_L")
        if method == "ppic":
            V = a.pop("blk:V")
            a["blk:Ksd"] = np.einsum("st,mtb->msb", L, V)
            a["blk:beta"] = np.zeros(V.shape[:2])
            a["blk:B"] = np.zeros(V.shape[:2] + V.shape[1:2])
        a["__store_schema__"] = np.int64(1)
        v1 = tmp_path / "v1.npz"
        with open(v1, "wb") as fh:
            np.savez(fh, **a)
        loaded = serialize.load_store(v1)
        for x, y in zip(jax.tree.leaves(store.to_state()),
                        jax.tree.leaves(loaded.to_state())):
            np.testing.assert_allclose(y, x, atol=1e-9)

    def test_server_checkpoint_swap(self, prob, runner, tmp_path):
        """Replica flow: server A checkpoints, server B (fitted on a
        RESCALED posterior) swaps it in and now serves A's posterior."""
        p = prob
        a = api.fit("ppitc", p["kfn"], p["params"], p["X"], p["y"],
                    S=p["S"], runner=runner)
        b = api.fit("ppitc", p["kfn"], p["params"], p["X"], 2.0 * p["y"],
                    S=p["S"], runner=runner)
        srv_a = GPServer(a, max_batch=8)
        srv_b = GPServer(b, max_batch=8)
        path = tmp_path / "replica.npz"
        srv_a.checkpoint(path)
        srv_b.swap_from_checkpoint(path)
        m_a, _ = srv_a.predict(p["U"][:8])
        m_b, _ = srv_b.predict(p["U"][:8])
        np.testing.assert_array_equal(m_a, m_b)
        assert srv_b.stats.n_state_swaps == 1

    def test_swap_from_checkpoint_detaches_stale_store(self, prob, runner,
                                                       tmp_path):
        """Restoring a checkpoint invalidates any attached store (it
        describes the pre-restore posterior); a later update() must demand
        a fresh store instead of silently reverting the restored state."""
        p = prob
        store = api.init_store("ppitc", p["kfn"], p["params"], p["X"],
                               p["y"], S=p["S"], runner=runner)
        srv = GPServer(api.FittedGP(api.get("ppitc"), p["kfn"], p["params"],
                                    store.to_state()),
                       max_batch=8, store=store)
        path = tmp_path / "restore.npz"
        serialize.save_state(path, store.retire(0).to_state())
        srv.swap_from_checkpoint(path)
        assert srv.store is None
        with pytest.raises(ValueError, match="StateStore"):
            srv.update(p["X"], p["y"])

    def test_routed_server_rejects_pitc_checkpoint(self, prob, runner,
                                                   tmp_path):
        p = prob
        pitc_state = ppitc.fit(p["kfn"], p["params"], p["X"], p["y"],
                               S=p["S"], runner=runner)
        path = serialize.save_state(tmp_path / "pitc.npz", pitc_state)
        pic_model = api.fit("ppic", p["kfn"], p["params"], p["X"], p["y"],
                            S=p["S"], runner=runner)
        srv = GPServer(pic_model, max_batch=8, routed=True)
        with pytest.raises(ValueError, match="centroids"):
            srv.swap_from_checkpoint(path)


# ---------------------------------------------------------------------------
# hyper satellite: custom objectives don't thread unused data; PITC NLML
# equals the literal centralized computation in float64
# ---------------------------------------------------------------------------

class TestHyperFix:
    def test_fit_requires_data_only_for_default_objective(self, prob):
        with pytest.raises(ValueError, match="needs \\(X, y\\)"):
            hyper.fit(prob["kfn"], prob["params"])

    def test_custom_objective_runs_without_data(self, prob):
        calls = []

        def obj(params):
            calls.append(1)
            return jnp.sum(params["log_lengthscale"] ** 2)

        params, losses = hyper.fit(prob["kfn"], prob["params"], steps=3,
                                   objective=obj)
        assert losses.shape == (3,) and calls

    def test_pitc_nlml_equals_literal_centralized_float64(self):
        """Tiny-data float64 gate: the distributable PITC likelihood equals
        -log N(y; 0, Gamma_DD + Lambda) computed literally (dense chol on
        the PITC train covariance)."""
        p = make_problem(n=24, u=4, s=6, M=3)
        r = VmapRunner(M=p["M"])
        par = hyper.pitc_nlml(p["kfn"], p["params"], p["S"], p["X"], p["y"],
                              r)
        # literal: Gamma = K_DS Kss^{-1} K_SD, Lambda = blockdiag(K - Gamma
        # + noise)
        Kss_L = linalg.chol(p["kfn"](p["params"], p["S"], p["S"]))
        Kds = p["kfn"](p["params"], p["X"], p["S"])
        Gamma = Kds @ linalg.chol_solve(Kss_L, Kds.T)
        Sig = cov.add_noise(p["kfn"](p["params"], p["X"], p["X"]),
                            p["params"]) - Gamma
        n, b = p["X"].shape[0], p["X"].shape[0] // p["M"]
        Cov = Gamma
        for m in range(p["M"]):
            sl = slice(m * b, (m + 1) * b)
            Cov = Cov.at[sl, sl].add(Sig[sl, sl])
        L = jnp.linalg.cholesky(Cov)
        quad = p["y"] @ linalg.chol_solve(L, p["y"][:, None])[:, 0]
        literal = 0.5 * (quad + linalg.logdet_from_chol(L)
                         + n * jnp.log(2 * jnp.pi))
        np.testing.assert_allclose(float(par), float(literal), rtol=1e-9)

    def test_fit_parallel_improves_without_passing_data_to_fit(self, prob):
        r = VmapRunner(M=prob["M"])
        p0 = cov.init_params(3, signal=0.5, noise=0.5, lengthscale=3.0,
                             dtype=jnp.float64)
        _, losses = hyper.fit_parallel(prob["kfn"], p0, prob["S"], prob["X"],
                                       prob["y"], r, steps=10, lr=0.08)
        assert float(losses[-1]) < float(losses[0])
