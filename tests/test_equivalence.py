"""Numerical verification of the paper's Theorems 1-3: the parallel methods
equal their centralized counterparts (float64, tolerances dominated by the
relative jitter in the PSD solves)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import icf, picf, pitc, ppic, ppitc
from repro.parallel.runner import VmapRunner

from helpers import block_diag_err, make_problem

TOL = 5e-6


@pytest.fixture(scope="module")
def prob():
    return make_problem()


@pytest.fixture(scope="module")
def runner(prob):
    return VmapRunner(M=prob["M"])


class TestTheorem1:
    def test_ppitc_equals_pitc(self, prob, runner):
        p = pitc.pitc_predict_literal(prob["kfn"], prob["params"], prob["S"],
                                      prob["X"], prob["y"], prob["U"],
                                      prob["M"])
        q = ppitc.predict(prob["kfn"], prob["params"], prob["S"], prob["X"],
                          prob["y"], prob["U"], runner)
        np.testing.assert_allclose(q.mean, p.mean, atol=TOL)
        assert block_diag_err(p.cov, q.blocks) < TOL

    def test_blockwise_centralized_matches(self, prob):
        p = pitc.pitc_predict_literal(prob["kfn"], prob["params"], prob["S"],
                                      prob["X"], prob["y"], prob["U"],
                                      prob["M"])
        q = pitc.pitc_predict_blockwise(prob["kfn"], prob["params"],
                                        prob["S"], prob["X"], prob["y"],
                                        prob["U"], prob["M"])
        np.testing.assert_allclose(q.mean, p.mean, atol=TOL)
        np.testing.assert_allclose(q.cov, p.cov, atol=TOL)

    def test_support_equals_data_recovers_fgp(self, prob, runner):
        """PITC with S = D is exact: Gamma_DD = K_DD, Lambda = noise I."""
        from repro.core import gp
        exact = gp.predict(prob["kfn"], prob["params"], prob["X"], prob["y"],
                           prob["U"])
        q = ppitc.predict(prob["kfn"], prob["params"], prob["X"], prob["X"],
                          prob["y"], prob["U"], runner)
        np.testing.assert_allclose(q.mean, exact.mean, atol=1e-4)


class TestTheorem2:
    def test_ppic_equals_pic(self, prob, runner):
        p = pitc.pic_predict_literal(prob["kfn"], prob["params"], prob["S"],
                                     prob["X"], prob["y"], prob["U"],
                                     prob["M"])
        q = ppic.predict(prob["kfn"], prob["params"], prob["S"], prob["X"],
                         prob["y"], prob["U"], runner)
        np.testing.assert_allclose(q.mean, p.mean, atol=TOL)
        assert block_diag_err(p.cov, q.blocks) < TOL

    def test_blockwise_centralized_matches(self, prob):
        p = pitc.pic_predict_literal(prob["kfn"], prob["params"], prob["S"],
                                     prob["X"], prob["y"], prob["U"],
                                     prob["M"])
        q = pitc.pic_predict_blockwise(prob["kfn"], prob["params"], prob["S"],
                                       prob["X"], prob["y"], prob["U"],
                                       prob["M"])
        np.testing.assert_allclose(q.mean, p.mean, atol=TOL)
        # blockwise returns a dense block-diagonal cov; compare its blocks
        M, u = prob["M"], prob["U"].shape[0]
        b = u // M
        blocks = jnp.stack([q.cov[m * b:(m + 1) * b, m * b:(m + 1) * b]
                            for m in range(M)])
        assert block_diag_err(p.cov, blocks) < TOL


@pytest.mark.parametrize("method,literal", [
    (ppitc, pitc.pitc_predict_literal), (ppic, pitc.pic_predict_literal)])
def test_collective_program_equals_literal(prob, runner, method, literal):
    """The fully-collective per-machine program (``predict_distributed``:
    the all-reduce and eqs. 7-8 / 12-14 inside one machine step) equals
    the centralized literal form, as the cached-state path does."""
    p = literal(prob["kfn"], prob["params"], prob["S"], prob["X"],
                prob["y"], prob["U"], prob["M"])
    q = method.predict_distributed(prob["kfn"], prob["params"], prob["S"],
                                   prob["X"], prob["y"], prob["U"], runner)
    np.testing.assert_allclose(q.mean, p.mean, atol=TOL)
    assert block_diag_err(p.cov, q.blocks) < TOL


class TestTheorem3:
    R = 48

    def test_distributed_factor_matches_centralized(self, prob, runner):
        fc = icf.icf_factor(prob["kfn"], prob["params"], prob["X"], self.R)
        fp = picf.factor(prob["kfn"], prob["params"], prob["X"], self.R,
                         runner)
        F = jnp.concatenate(list(fp.F), axis=1)
        np.testing.assert_allclose(F, fc.F, atol=1e-9)

    def test_picf_equals_icf(self, prob, runner):
        fc = icf.icf_factor(prob["kfn"], prob["params"], prob["X"], self.R)
        p = icf.icf_predict_literal(prob["kfn"], prob["params"], prob["X"],
                                    prob["y"], prob["U"], fc.F)
        q = picf.predict(prob["kfn"], prob["params"], prob["X"], prob["y"],
                         prob["U"], self.R, runner)
        np.testing.assert_allclose(q.mean, p.mean, atol=1e-9)
        np.testing.assert_allclose(q.cov, p.cov, atol=1e-9)

    def test_picf_sharded_u_matches(self, prob, runner):
        fc = icf.icf_factor(prob["kfn"], prob["params"], prob["X"], self.R)
        p = icf.icf_predict_literal(prob["kfn"], prob["params"], prob["X"],
                                    prob["y"], prob["U"], fc.F)
        q = picf.predict(prob["kfn"], prob["params"], prob["X"], prob["y"],
                         prob["U"], self.R, runner, shard_u=True)
        np.testing.assert_allclose(q.mean, p.mean, atol=1e-9)
        assert block_diag_err(p.cov, q.blocks) < 1e-9

    def test_full_rank_icf_recovers_fgp(self, prob, runner):
        """R = |D| makes the ICF exact, so pICF == FGP."""
        from repro.core import gp
        exact = gp.predict(prob["kfn"], prob["params"], prob["X"], prob["y"],
                           prob["U"])
        q = picf.predict(prob["kfn"], prob["params"], prob["X"], prob["y"],
                         prob["U"], prob["X"].shape[0], runner)
        np.testing.assert_allclose(q.mean, exact.mean, atol=1e-5)
        np.testing.assert_allclose(q.cov, exact.cov, atol=1e-5)
