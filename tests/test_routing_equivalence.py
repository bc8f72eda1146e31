"""Property-based equivalence suite for centroid-routed pPIC serving.

The served posterior must be a pure function of (query point, fitted state):
Remark 2 says a query belongs to the block whose local data best explains
it, not to whichever block its *position in the arriving batch* happens to
map to. The properties locked down here:

* permutation invariance — bitwise: reordering a query batch permutes the
  outputs and changes nothing else (same shapes -> same executable -> same
  floating-point program per row);
* re-chunking invariance — serving the same query set in chunks of any size
  (different shapes, hence different padded executables) agrees to float64
  roundoff;
* centralized equivalence — routed pPIC from cached factors equals the
  literal centralized PIC oracle (eqs. 15-18) with eq. (18)'s i = m branch
  selected by the same nearest-centroid assignment;
* the positional path is *not* composition-invariant (the motivating bug);
* the routed GPServer resolves every ticket to the routed posterior no
  matter the arrival order.

The properties set ``deadline=None``: each one's first example compiles
the serving program, and a compile time says nothing about the property.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import api, pitc, ppic
from repro.launch.gp_serve import GPServer
from repro.parallel.runner import (VmapRunner, gather_two_bucket,
                                   routed_capacity, scatter_two_bucket)

from helpers import make_problem

ORACLE_TOL = 5e-6       # matches tests/test_equivalence.py (PSD-solve jitter)
RECHUNK_TOL = 1e-10     # float64 roundoff across differently-padded shapes


@pytest.fixture(scope="module")
def prob():
    return make_problem()


@pytest.fixture(scope="module")
def state(prob):
    return ppic.fit(prob["kfn"], prob["params"], prob["X"], prob["y"],
                    S=prob["S"], runner=VmapRunner(M=prob["M"]))


@pytest.fixture(scope="module")
def base(prob, state):
    """Reference routed posterior of the full query set, served whole."""
    return ppic.predict_routed_diag(prob["kfn"], prob["params"], state,
                                    prob["U"])


class TestRoutingInvariance:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_permutation_is_bitwise_invariant(self, prob, state, base, seed):
        perm = np.random.RandomState(seed).permutation(prob["U"].shape[0])
        m, v = ppic.predict_routed_diag(prob["kfn"], prob["params"], state,
                                        prob["U"][perm])
        np.testing.assert_array_equal(np.asarray(m), np.asarray(base[0])[perm])
        np.testing.assert_array_equal(np.asarray(v), np.asarray(base[1])[perm])

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1),
           chunk=st.integers(min_value=1, max_value=11))
    def test_rechunking_is_invariant(self, prob, state, base, seed, chunk):
        """Permute AND re-chunk: serving the set in arbitrary microbatches
        reproduces the whole-batch posterior (shapes differ, so only
        roundoff-level agreement is guaranteed)."""
        u = prob["U"].shape[0]
        perm = np.random.RandomState(seed).permutation(u)
        Up = prob["U"][perm]
        parts = [ppic.predict_routed_diag(prob["kfn"], prob["params"], state,
                                          Up[i:i + chunk])
                 for i in range(0, u, chunk)]
        m = jnp.concatenate([p[0] for p in parts])
        v = jnp.concatenate([p[1] for p in parts])
        np.testing.assert_allclose(m, np.asarray(base[0])[perm],
                                   atol=RECHUNK_TOL)
        np.testing.assert_allclose(v, np.asarray(base[1])[perm],
                                   atol=RECHUNK_TOL)

    def test_routing_is_pure_in_the_query(self, prob, state):
        """Assignment of a query never depends on its neighbours."""
        whole = np.asarray(ppic.route_queries(state, prob["U"]))
        for i in range(prob["U"].shape[0]):
            one = np.asarray(ppic.route_queries(state, prob["U"][i:i + 1]))
            assert one[0] == whole[i]

    def test_positional_path_is_composition_dependent(self, prob, state):
        """The motivating defect: predict_batch_diag's per-query posterior
        moves when the batch is permuted (queries land on other blocks)."""
        m, _ = ppic.predict_batch_diag(prob["kfn"], prob["params"], state,
                                       prob["U"])
        perm = np.random.RandomState(0).permutation(prob["U"].shape[0])
        mp, _ = ppic.predict_batch_diag(prob["kfn"], prob["params"], state,
                                        prob["U"][perm])
        assert float(jnp.abs(mp - jnp.asarray(np.asarray(m)[perm])).max()) \
            > 1e-6


class TestRoutedEqualsCentralizedPIC:
    def test_matches_routed_literal_oracle(self, prob, state, base):
        """Thm 2 + Remark 2: cached-factor routed pPIC == literal centralized
        PIC with the same per-query block choice in eq. (18)."""
        assign = ppic.route_queries(state, prob["U"])
        oracle = pitc.pic_predict_literal_routed(
            prob["kfn"], prob["params"], prob["S"], prob["X"], prob["y"],
            prob["U"], prob["M"], assign)
        np.testing.assert_allclose(base[0], oracle.mean, atol=ORACLE_TOL)
        np.testing.assert_allclose(base[1], jnp.diag(oracle.cov),
                                   atol=ORACLE_TOL)

    def test_full_cov_view_agrees_with_diag(self, prob, state, base):
        post = ppic.predict_routed(prob["kfn"], prob["params"], state,
                                   prob["U"])
        np.testing.assert_allclose(post.mean, base[0], atol=1e-12)
        np.testing.assert_allclose(jnp.diag(post.cov), base[1], atol=1e-10)

    def test_within_block_cov_matches_oracle(self, prob, state):
        """Same-block off-diagonal entries come from eqs. (12)-(14) too."""
        assign = np.asarray(ppic.route_queries(state, prob["U"]))
        post = ppic.predict_routed(prob["kfn"], prob["params"], state,
                                   prob["U"])
        oracle = pitc.pic_predict_literal_routed(
            prob["kfn"], prob["params"], prob["S"], prob["X"], prob["y"],
            prob["U"], prob["M"], assign)
        same = assign[:, None] == assign[None, :]
        diff = np.abs(np.asarray(post.cov) - np.asarray(oracle.cov))
        assert float(diff[same].max()) < ORACLE_TOL


class TestTwoBucketScatter:
    """The capacity-bounded routed layout (runner.scatter_two_bucket): the
    serving path computes (M + G)·cap rows instead of M·|U| but must emit
    THE SAME posterior as the capacity-|U| layout, because every predictive
    equation is row-independent and overflow groups carry their block's
    factors.

    Bitwise equality across the two layouts is asserted in float32 (the
    serving dtype). In float64 the layouts differ by LAPACK-width roundoff
    only (~1e-13): CPU trsm picks its column-panel strategy from the TOTAL
    RHS width, so a (b, cap) solve and a (b, |U|) solve give per-column
    results that agree to roundoff, not bit-for-bit. WITHIN a layout,
    permutation invariance stays bitwise in both dtypes (the core PR-2
    property, preserved by keeping every query-axis contraction row-major —
    see _block_posterior_diag)."""

    F64_LAYOUT_TOL = 1e-12

    @pytest.fixture(scope="class")
    def prob32(self):
        return make_problem(dtype=jnp.float32)

    @pytest.fixture(scope="class")
    def state32(self, prob32):
        return ppic.fit(prob32["kfn"], prob32["params"], prob32["X"],
                        prob32["y"], S=prob32["S"],
                        runner=VmapRunner(M=prob32["M"]))

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_two_bucket_equals_capacity_layout_bitwise_f32(self, prob32,
                                                           state32, seed):
        perm = np.random.RandomState(seed).permutation(
            prob32["U"].shape[0])
        Up = prob32["U"][perm]
        m_c, v_c = ppic.predict_routed_diag_capacity(
            prob32["kfn"], prob32["params"], state32, Up)
        m_t, v_t = ppic.predict_routed_diag(prob32["kfn"], prob32["params"],
                                            state32, Up)
        np.testing.assert_array_equal(np.asarray(m_t), np.asarray(m_c))
        np.testing.assert_array_equal(np.asarray(v_t), np.asarray(v_c))

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_two_bucket_equals_capacity_layout_f64(self, prob, state, seed):
        perm = np.random.RandomState(seed).permutation(prob["U"].shape[0])
        Up = prob["U"][perm]
        m_c, v_c = ppic.predict_routed_diag_capacity(
            prob["kfn"], prob["params"], state, Up)
        m_t, v_t = ppic.predict_routed_diag(prob["kfn"], prob["params"],
                                            state, Up)
        np.testing.assert_allclose(m_t, m_c, atol=self.F64_LAYOUT_TOL)
        np.testing.assert_allclose(v_t, v_c, atol=self.F64_LAYOUT_TOL)

    def test_skewed_traffic_overflows_and_still_matches(self, prob32,
                                                        state32):
        """All queries on one centroid: the main bucket overflows into the
        skew groups, which must serve the SAME block program (bitwise)."""
        c0 = np.asarray(state32.centroids)[0]
        rng = np.random.RandomState(7)
        Uskew = jnp.asarray(
            c0[None, :] + 0.01 * rng.randn(20, c0.shape[0]).astype("f4"))
        assign = np.asarray(ppic.route_queries(state32, Uskew))
        assert (assign == assign[0]).all()          # genuinely skewed
        cap, G = routed_capacity(20, prob32["M"])
        assert G > 0 and cap < 20                   # overflow exercised
        m_c, v_c = ppic.predict_routed_diag_capacity(
            prob32["kfn"], prob32["params"], state32, Uskew)
        m_t, v_t = ppic.predict_routed_diag(prob32["kfn"], prob32["params"],
                                            state32, Uskew)
        np.testing.assert_array_equal(np.asarray(m_t), np.asarray(m_c))
        np.testing.assert_array_equal(np.asarray(v_t), np.asarray(v_c))

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1),
           n=st.integers(min_value=1, max_value=40),
           m=st.integers(min_value=1, max_value=9))
    def test_scatter_gather_roundtrip(self, seed, n, m):
        """Every row lands in exactly one bucket slot and gathers back."""
        rng = np.random.RandomState(seed)
        X = jnp.asarray(rng.randn(n, 3))
        assign = jnp.asarray(rng.randint(0, m, size=n))
        lay = scatter_two_bucket(X, assign, m)
        # row identity: first coordinate survives the scatter+gather
        out = gather_two_bucket(lay.Xb[..., 0],
                                None if lay.Xo is None else lay.Xo[..., 0],
                                lay)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(X[:, 0]))
        # overflow groups are single-block: each occupied slot's row was
        # assigned to the group's recorded block
        if lay.Xo is not None:
            a = np.asarray(assign)
            o_blk = np.asarray(lay.o_blk)
            order = np.asarray(lay.order)
            for j in range(n):
                if not bool(lay.in_main[j]):
                    assert a[order[j]] == o_blk[int(lay.group[j])]

    def test_padded_rows_reduction_at_m8(self):
        """ISSUE acceptance: >= 2x fewer computed rows than capacity-|U| at
        M=8 balanced traffic (alpha=2: (8+4)·cap vs 8·n)."""
        for n in (32, 64, 256):
            cap, G = routed_capacity(n, 8)
            assert 8 * n / ((8 + G) * cap) >= 2.0

    def test_tile_alignment(self):
        cap, _ = routed_capacity(50, 8, tile=16)
        assert cap % 16 == 0


class TestRegistryAndServer:
    def test_registry_exposes_routed_for_pic_family(self, prob):
        assert api.get("ppic").predict_routed_diag_fn is not None
        assert api.get("pic").predict_routed_diag_fn is not None
        assert api.get("ppitc").predict_routed_diag_fn is None

    def test_fitted_gp_routed_guard(self, prob):
        runner = VmapRunner(M=prob["M"])
        model = api.fit("ppitc", prob["kfn"], prob["params"], prob["X"],
                        prob["y"], S=prob["S"], runner=runner)
        with pytest.raises(ValueError, match="no routed prediction"):
            model.predict_routed_diag(prob["U"])

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_server_resolves_tickets_order_independently(self, prob, state,
                                                         seed):
        """Routed GPServer: any arrival order yields the same per-ticket
        posterior (bitwise) as the server's own compiled predict on the
        whole set. The reference goes through the SAME jitted function the
        flush dispatches — XLA's jit fuses covariance assembly differently
        from op-by-op eager execution (1-ulp differences in K_US itself),
        so eager-vs-jit bit equality was never the property; arrival-order
        independence of the compiled program is."""
        model = api.FittedGP(api.get("ppic"), prob["kfn"], prob["params"],
                             state)
        srv = GPServer(model, max_batch=8, routed=True)
        perm = np.random.RandomState(seed).permutation(8)
        tickets = {int(i): srv.submit(prob["U"][int(i)]) for i in perm}
        ref_m, ref_v = srv.plan.routed_diag(prob["U"][:8])
        for i in range(8):
            m, v = srv.result(tickets[i])
            np.testing.assert_array_equal(np.asarray(m), np.asarray(ref_m[i]))
            np.testing.assert_array_equal(np.asarray(v), np.asarray(ref_v[i]))
