"""Elastic multi-tenant serving runtime (repro/serving/).

Acceptance (ISSUE 8):

* a tenant served through the multiplexed runtime is BITWISE-equal (f32)
  to the same tenant served alone through its own ``GPServer`` — the
  single-tenant server IS a one-tenant client of the scheduler, and
  multiplexing other tenants in between must not perturb anyone's batches;
* plan-compatible tenants share ONE executable lineage: the trace-count
  probe shows zero recompiles across tenant interleavings at fixed shapes;
* weighted-deadline dispatch: earliest weighted due time first, no
  starvation under skewed weights, ordering invariant under submission
  permutation (hypothesis properties);
* admission control (reject / shed_oldest) and the adaptive flusher are
  observable through per-tenant ``ServeStats`` and the fleet rollup;
* a ``save_store(..., spec=...)`` artifact re-admits the whole deployment
  (``TenantRegistry.admit_from_checkpoint``), bitwise.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import api, serialize
from repro.core import covariance as cov
from repro.launch.gp_serve import GPServer
from repro.parallel.runner import VmapRunner
from repro.serving import (AdaptiveDeadline, AdmissionError, Ema, Reservoir,
                           ServeStats, TenantRegistry, TenantScheduler,
                           lineage_key, rollup)

from helpers import make_problem


@pytest.fixture(scope="module")
def prob():
    return make_problem(dtype=jnp.float32)


@pytest.fixture(scope="module")
def runner(prob):
    return VmapRunner(M=prob["M"])


def _fit(prob, runner, *, roll=0):
    """A ppic posterior; ``roll`` shifts y so tenants differ in VALUES
    while keeping identical tree structure (the lineage-sharing case)."""
    y = jnp.roll(prob["y"], roll)
    return api.fit("ppic", prob["kfn"], prob["params"], prob["X"], y,
                   S=prob["S"], runner=runner)


@pytest.fixture(scope="module")
def models(prob, runner):
    return [_fit(prob, runner, roll=r) for r in (0, 7, 19)]


def _sched(clock):
    return TenantScheduler(clock=clock)


# ---------------------------------------------------------------------------
# Registry: membership + lineage dedup
# ---------------------------------------------------------------------------

class TestRegistryLineage:
    def test_compatible_tenants_share_one_lineage(self, models):
        spec = api.ServeSpec(max_batch=8)
        reg = TenantRegistry()
        a = reg.admit("a", models[0], spec)
        b = reg.admit("b", models[1], spec)
        assert reg.n_lineages == 1
        assert a.plan._exec is b.plan._exec
        assert a.plan.stats is b.plan.stats
        # independent posteriors: the shared executables, not shared state
        assert a.plan.state is models[0].state
        assert b.plan.state is models[1].state
        assert lineage_key(models[0], spec) == lineage_key(models[1], spec)

    def test_incompatible_specs_fork_lineages(self, models):
        reg = TenantRegistry()
        a = reg.admit("a", models[0], api.ServeSpec(max_batch=8))
        b = reg.admit("b", models[1], api.ServeSpec(max_batch=16))
        assert reg.n_lineages == 2
        assert a.plan._exec is not b.plan._exec

    def test_zero_recompiles_across_interleavings(self, prob, models):
        """The acceptance probe: after each tenant has served one batch of
        a given shape, ANY further interleaving of tenants at fixed shapes
        adds zero traces to the shared lineage."""
        spec = api.ServeSpec(max_batch=8)
        sched = _sched(lambda: 0.0)
        for tid, m in zip("abc", models):
            sched.admit(tid, m, spec)
        U = prob["U"][:5]
        sched.predict("a", U)               # first dispatch pays the traces
        traces = sched.registry.get("a").plan.stats.n_traces
        for tid in "bacbcabccba":
            sched.predict(tid, U)
        assert sched.registry.get("a").plan.stats.n_traces == traces

    def test_evict_keeps_lineage_for_survivors(self, prob, models):
        spec = api.ServeSpec(max_batch=8)
        sched = _sched(lambda: 0.0)
        sched.admit("a", models[0], spec)
        sched.admit("b", models[1], spec)
        sched.predict("b", prob["U"][:5])
        traces = sched.registry.get("b").plan.stats.n_traces
        sched.evict("a")
        assert "a" not in sched.registry and len(sched.registry) == 1
        assert sched.registry.n_lineages == 1
        # re-admission rejoins the surviving lineage: still zero recompiles
        sched.admit("a2", models[2], spec)
        sched.predict("a2", prob["U"][:5])
        assert sched.registry.get("a2").plan.stats.n_traces == traces

    def test_evict_drains_pending_tickets(self, prob, models):
        sched = _sched(lambda: 0.0)
        sched.admit("a", models[0], api.ServeSpec(max_batch=8))
        t = sched.submit("a", prob["U"][0])
        rec = sched.evict("a")
        assert t in rec.ready          # drained, not abandoned
        with pytest.raises(KeyError, match="unknown tenant"):
            sched.submit("a", prob["U"][0])

    def test_admission_guards(self, prob, runner, models):
        reg = TenantRegistry()
        reg.admit("a", models[0], api.ServeSpec(max_batch=8))
        with pytest.raises(ValueError, match="already admitted"):
            reg.admit("a", models[1], api.ServeSpec(max_batch=8))
        with pytest.raises(ValueError, match="weight"):
            reg.admit("w", models[1], api.ServeSpec(max_batch=8), weight=0.0)
        with pytest.raises(ValueError, match="overflow"):
            reg.admit("o", models[1], api.ServeSpec(max_batch=8),
                      overflow="drop_newest")
        ppitc = api.fit("ppitc", prob["kfn"], prob["params"], prob["X"],
                        prob["y"], S=prob["S"], runner=runner)
        with pytest.raises(ValueError, match="predict_routed_diag"):
            reg.admit("r", ppitc, api.ServeSpec(max_batch=8, routed=True))

    def test_rebind_swaps_one_tenant_only(self, prob, models):
        spec = api.ServeSpec(max_batch=8)
        sched = _sched(lambda: 0.0)
        sched.admit("a", models[0], spec)
        sched.admit("b", models[1], spec)
        U = prob["U"][:5]
        mb0, vb0 = sched.predict("b", U)
        traces = sched.registry.get("a").plan.stats.n_traces
        sched.swap_state("a", models[2].state)
        ma, va = sched.predict("a", U)
        mref, vref = sched.predict("b", U)    # b untouched, bitwise
        np.testing.assert_array_equal(np.asarray(mref), np.asarray(mb0))
        np.testing.assert_array_equal(np.asarray(vref), np.asarray(vb0))
        # the swap rebound, it did not recompile
        assert sched.registry.get("a").plan.stats.n_traces == traces
        assert sched.stats("a").n_state_swaps == 1


# ---------------------------------------------------------------------------
# Bitwise multiplexed-vs-isolated equivalence (the ground truth)
# ---------------------------------------------------------------------------

def _mux_vs_isolated(prob, models, events, *, deadline_ms=50.0,
                     max_batch=4, pump_every=3):
    """Drive the same per-tenant event sequence through (1) one multiplexed
    scheduler and (2) one isolated GPServer per tenant, on the same virtual
    clock, and require bitwise-identical results per ticket."""
    tids = sorted({tid for tid, _ in events})
    clk = [0.0]
    clock = lambda: clk[0]
    sched = _sched(clock)
    for i, tid in enumerate(tids):
        sched.admit(tid, models[i], api.ServeSpec(max_batch=max_batch),
                    flush_deadline_ms=deadline_ms)
    solo = {tid: GPServer(models[i], spec=api.ServeSpec(max_batch=max_batch),
                          flush_deadline_ms=deadline_ms, clock=clock)
            for i, tid in enumerate(tids)}
    mux_tickets, solo_tickets = [], []
    for step, (tid, dt) in enumerate(events):
        clk[0] += dt
        x = prob["U"][step % prob["U"].shape[0]]
        mux_tickets.append((tid, sched.submit(tid, x)))
        solo_tickets.append((tid, solo[tid].submit(x)))
        if step % pump_every == pump_every - 1:
            sched.pump()
            for srv in solo.values():
                srv.pump()
    for (tid, tk_m), (_, tk_s) in zip(mux_tickets, solo_tickets):
        assert tk_m == tk_s            # per-tenant ticket namespaces agree
        mm, vm = sched.result(tid, tk_m)
        ms, vs = solo[tid].result(tk_s)
        np.testing.assert_array_equal(np.asarray(mm), np.asarray(ms))
        np.testing.assert_array_equal(np.asarray(vm), np.asarray(vs))


class TestBitwiseEquivalence:
    def test_multiplexed_equals_isolated_interleaved(self, prob, models):
        events = [("a", 0.001), ("b", 0.0), ("a", 0.002), ("c", 0.001),
                  ("b", 0.0), ("a", 0.0), ("c", 0.03), ("b", 0.001),
                  ("a", 0.06), ("b", 0.0), ("c", 0.0), ("a", 0.001),
                  ("b", 0.002), ("c", 0.001), ("a", 0.0), ("b", 0.03)]
        _mux_vs_isolated(prob, models, events)

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_multiplexed_equals_isolated_random_traffic(self, prob, models,
                                                        seed):
        r = np.random.RandomState(seed)
        tids = ["a", "b", "c"]
        events = [(tids[r.randint(3)], float(r.choice([0.0, 1e-3, 0.03])))
                  for _ in range(24)]
        _mux_vs_isolated(prob, models, events,
                         max_batch=int(r.choice([3, 4, 8])))


# ---------------------------------------------------------------------------
# Weighted-deadline scheduling properties
# ---------------------------------------------------------------------------

class TestSchedulerProperties:
    @settings(max_examples=8, deadline=None)
    @given(heavy=st.floats(min_value=1.0, max_value=64.0),
           light=st.floats(min_value=0.1, max_value=1.0))
    def test_no_starvation_under_skewed_weights(self, prob, models, heavy,
                                                light):
        """A due tenant is never passed over: however skewed the weights,
        every pump flushes EVERY tenant whose weighted due time passed, so
        the light tenant's staleness stays bounded by deadline/weight +
        one pump period."""
        clk = [0.0]
        period = 0.004
        sched = _sched(lambda: clk[0])
        sched.admit("heavy", models[0], api.ServeSpec(max_batch=64),
                    weight=heavy, flush_deadline_ms=10.0)
        sched.admit("light", models[1], api.ServeSpec(max_batch=64),
                    weight=light, flush_deadline_ms=10.0)
        sched.submit("light", prob["U"][0])
        due = 10e-3 / light
        i = 0
        while clk[0] <= due + period:         # heavy keeps the queue warm
            sched.submit("heavy", prob["U"][i % 8])
            clk[0] += period
            sched.pump()
            i += 1
        # light was due at 10ms/light; the first pump at/after that flushed
        assert sched.pending("light") == 0
        assert sched.stats("light").n_deadline_flushes >= 1
        assert any(e[0] == "light" for e in sched.dispatch_log)
        assert sched.stats("light").staleness.percentile(99) \
            <= (due + period) * 1e3 + 1e-6

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_dispatch_order_invariant_under_submission_permutation(
            self, prob, models, seed):
        """pump() drains due tenants by (weighted due time, admission seq),
        NOT by submission arrival order: permuting which tenant submitted
        first within the window leaves the dispatch order unchanged."""
        weights = {"a": 1.0, "b": 2.0, "c": 4.0}

        def run(order):
            clk = [0.0]
            sched = _sched(lambda: clk[0])
            for i, tid in enumerate(sorted(weights)):
                sched.admit(tid, models[i], api.ServeSpec(max_batch=64),
                            weight=weights[tid], flush_deadline_ms=20.0)
            for tid in order:              # same instant, permuted order
                sched.submit(tid, prob["U"][0])
            clk[0] += 1.0                  # everyone long past due
            sched.pump()
            return [tid for tid, _, _ in sched.dispatch_log]

        base = run(["a", "b", "c"])
        perm = list(np.random.RandomState(seed).permutation(["a", "b", "c"]))
        assert run(perm) == base
        # and the order is weighted-due order: heaviest weight due first
        assert base == ["c", "b", "a"]

    def test_pump_returns_total_resolved(self, prob, models):
        clk = [0.0]
        sched = _sched(lambda: clk[0])
        sched.admit("a", models[0], api.ServeSpec(max_batch=64),
                    flush_deadline_ms=5.0)
        sched.admit("b", models[1], api.ServeSpec(max_batch=64),
                    flush_deadline_ms=5.0)
        for i in range(3):
            sched.submit("a", prob["U"][i])
        sched.submit("b", prob["U"][3])
        assert sched.pump() == 0           # nothing due yet
        clk[0] += 0.01
        assert sched.pump() == 4
        assert sched.pump() == 0


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------

class TestAdmissionControl:
    def test_reject_policy_raises_and_counts(self, prob, models):
        sched = _sched(lambda: 0.0)
        sched.admit("a", models[0], api.ServeSpec(max_batch=64),
                    max_pending=2, overflow="reject")
        t0 = sched.submit("a", prob["U"][0])
        sched.submit("a", prob["U"][1])
        with pytest.raises(AdmissionError, match="max_pending=2"):
            sched.submit("a", prob["U"][2])
        st_ = sched.stats("a")
        assert st_.n_rejected == 1 and st_.n_requests == 2
        assert sched.pending("a") == 2     # queue untouched by the reject
        # draining reopens admission, and ticket ids stay contiguous
        sched.flush("a")
        assert sched.submit("a", prob["U"][2]) == t0 + 2

    def test_shed_oldest_policy_drops_and_counts(self, prob, models):
        sched = _sched(lambda: 0.0)
        sched.admit("a", models[0], api.ServeSpec(max_batch=64),
                    max_pending=2, overflow="shed_oldest")
        t0 = sched.submit("a", prob["U"][0])
        t1 = sched.submit("a", prob["U"][1])
        t2 = sched.submit("a", prob["U"][2])   # sheds t0
        assert sched.stats("a").n_shed == 1
        assert sched.pending("a") == 2
        sched.flush("a")
        sched.result("a", t1)
        sched.result("a", t2)
        with pytest.raises(KeyError, match="shed"):
            sched.result("a", t0)


# ---------------------------------------------------------------------------
# Adaptive flusher
# ---------------------------------------------------------------------------

class TestAdaptiveDeadline:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="gain"):
            AdaptiveDeadline(gain=0.0)

    def test_effective_deadline_tracks_interarrival(self, prob, models):
        clk = [0.0]
        sched = _sched(lambda: clk[0])
        sched.admit("a", models[0], api.ServeSpec(max_batch=64),
                    flush_deadline_ms=100.0,
                    adaptive=AdaptiveDeadline(gain=2.0, floor_ms=0.5))
        # no interarrival data yet: the declared budget is in force
        sched.submit("a", prob["U"][0])
        assert sched.effective_deadline_ms("a") == 100.0
        # brisk traffic (1ms spacing) tightens it toward gain*EMA = ~2ms
        for i in range(8):
            clk[0] += 0.001
            sched.submit("a", prob["U"][i % 8])
        eff = sched.effective_deadline_ms("a")
        assert eff == pytest.approx(2.0, rel=0.05)
        sched.flush("a")
        # a tightened deadline actually drives earlier deadline flushes
        sched.submit("a", prob["U"][0])
        clk[0] += 0.005                     # 5ms < 100ms budget, > ~2ms eff
        assert sched.pump() == 1
        assert sched.stats("a").n_deadline_flushes >= 1

    def test_sparse_traffic_relaxes_to_declared_budget(self, prob, models):
        clk = [0.0]
        sched = _sched(lambda: clk[0])
        sched.admit("a", models[0], api.ServeSpec(max_batch=64),
                    flush_deadline_ms=10.0, adaptive=True)
        sched.submit("a", prob["U"][0])
        clk[0] += 5.0                       # huge interarrival
        sched.flush("a")
        sched.submit("a", prob["U"][1])
        # gain*EMA is seconds-scale, so the budget caps it
        assert sched.effective_deadline_ms("a") == 10.0

    def test_floor_bounds_the_tightening(self, prob, models):
        clk = [0.0]
        sched = _sched(lambda: clk[0])
        sched.admit("a", models[0], api.ServeSpec(max_batch=64),
                    flush_deadline_ms=100.0,
                    adaptive=AdaptiveDeadline(gain=4.0, floor_ms=3.0))
        for i in range(10):                 # near-zero interarrival
            clk[0] += 1e-6
            sched.submit("a", prob["U"][i % 8])
        assert sched.effective_deadline_ms("a") == 3.0


# ---------------------------------------------------------------------------
# Observability: stats primitives + fleet rollup
# ---------------------------------------------------------------------------

class TestStatsAndRollup:
    def test_ema_none_seeding(self):
        e = Ema(alpha=0.5)
        assert e.value is None and e.get(7.0) == 7.0
        assert e.update(0.0) == 0.0         # 0.0 is a legal first sample
        assert e.update(2.0) == 1.0

    def test_reservoir_bounded_and_deterministic(self):
        r1, r2 = Reservoir(cap=16, seed=3), Reservoir(cap=16, seed=3)
        for i in range(1000):
            r1.record(float(i)); r2.record(float(i))
        assert r1.n_seen == 1000 and len(r1._buf) == 16
        assert r1.snapshot() == r2.snapshot()
        assert 0.0 <= r1.percentile(50) <= 999.0

    def test_g_hist_records_routed_ladder_usage(self, prob, models):
        sched = _sched(lambda: 0.0)
        sched.admit("a", models[0],
                    api.ServeSpec(max_batch=8, routed=True))
        for i in range(8):
            sched.submit("a", prob["U"][i])  # size flush at 8
        st_ = sched.stats("a")
        assert st_.n_size_flushes == 1
        assert sum(st_.g_hist.values()) == 1
        if 0 in st_.g_hist:
            assert st_.n_g0_flushes == st_.g_hist[0]

    def test_rollup_totals_and_snapshots(self, prob, models):
        clk = [0.0]
        sched = _sched(lambda: clk[0])
        sched.admit("a", models[0], api.ServeSpec(max_batch=4))
        sched.admit("b", models[1], api.ServeSpec(max_batch=4))
        for i in range(4):
            clk[0] += 0.001
            sched.submit("a", prob["U"][i])   # size flush
        sched.submit("b", prob["U"][0])
        sched.flush("b")
        r = sched.rollup()
        assert r["n_tenants"] == 2
        assert r["totals"]["n_requests"] == 5
        assert r["totals"]["n_flushes"] == 2
        snap = r["tenants"]["a"]
        assert snap["n_size_flushes"] == 1
        assert snap["staleness_ms"]["n"] == 4
        assert snap["staleness_ms"]["p99"] >= snap["staleness_ms"]["p50"]
        assert snap["interarrival_ms"] == pytest.approx(1.0)

    def test_staleness_ends_where_the_flush_is_dispatched(self, prob, models,
                                                         monkeypatch):
        """Staleness is submit -> flush dispatch: the time the dispatch
        itself takes is not queue time."""
        clk = [0.0]
        sched = _sched(lambda: clk[0])
        sched.admit("a", models[0], api.ServeSpec(max_batch=8))
        real = sched._dispatch

        def slow(t, U):
            clk[0] += 0.5            # the dispatch takes 500 ms
            return real(t, U)

        monkeypatch.setattr(sched, "_dispatch", slow)
        sched.submit("a", prob["U"][0])
        clk[0] = 0.002
        sched.submit("a", prob["U"][1])
        clk[0] = 0.010
        sched.flush("a")
        samples = sorted(sched.stats("a").staleness._buf)
        assert samples == pytest.approx([8.0, 10.0])

    def test_gpserver_stats_is_serving_stats(self, prob, models):
        """GPServer re-exports ServeStats from serving/ — one stats schema
        for single- and multi-tenant serving."""
        from repro.launch.gp_serve import ServeStats as ReExported
        assert ReExported is ServeStats
        srv = GPServer(models[0], max_batch=4)
        t = srv.submit(prob["U"][0])
        srv.flush()
        srv.result(t)
        assert isinstance(srv.stats, ServeStats)
        assert srv.stats.n_manual_flushes == 1
        assert rollup({"default": srv.stats})["totals"]["n_requests"] == 1


# ---------------------------------------------------------------------------
# Checkpoint -> re-admission (satellite: spec rides with the store)
# ---------------------------------------------------------------------------

class TestCheckpointReadmission:
    def _store_server(self, prob, runner, **srv_kw):
        p = prob
        n1 = p["X"].shape[0] // 2
        store = api.init_store("ppic", p["kfn"], p["params"], p["X"][:n1],
                               p["y"][:n1], S=p["S"], runner=runner)
        model = api.FittedGP(api.get("ppic"), p["kfn"], p["params"],
                             store.to_state())
        return GPServer(model, store=store, **srv_kw)

    def test_admit_from_checkpoint_bitwise(self, prob, runner, tmp_path):
        spec = api.ServeSpec(max_batch=8, routed=True)
        srv = self._store_server(prob, runner, spec=spec)
        path = tmp_path / "tenant.store.npz"
        srv.checkpoint_store(path)
        assert serialize.peek_store(path)["serve_spec"]["routed"] is True
        reg = TenantRegistry()
        t = reg.admit_from_checkpoint("restored", path)
        assert t.spec == spec              # policy reconstructed, not guessed
        m0, v0 = srv.predict(prob["U"][:6])
        m1, v1 = t.plan.routed_diag(prob["U"][:6])
        np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        # the restored tenant resumes ASSIMILATING, not just serving
        sched = TenantScheduler(reg)
        n1 = prob["X"].shape[0] // 2
        sched.commit_store("restored",
                           t.store.assimilate(prob["X"][n1:], prob["y"][n1:]))
        assert sched.stats("restored").n_updates == 1

    def test_missing_spec_fails_loudly(self, prob, runner, tmp_path):
        srv = self._store_server(prob, runner, max_batch=8)
        path = tmp_path / "bare.store.npz"
        serialize.save_store(path, srv.store)          # no spec embedded
        reg = TenantRegistry()
        with pytest.raises(ValueError, match="no ServeSpec"):
            reg.admit_from_checkpoint("t", path)
        # explicit override still admits
        t = reg.admit_from_checkpoint("t", path,
                                      spec=api.ServeSpec(max_batch=8))
        assert t.max_batch == 8

    def test_spec_meta_roundtrip_with_kernel_spec(self, tmp_path):
        spec = api.ServeSpec(kernel=cov.KernelSpec("se", "jnp", False, 16),
                             buckets=(8, 32), routed=True, alpha=3,
                             cached_cinv=True, dtype="state")
        meta = serialize._spec_meta(spec)
        assert serialize._spec_from_meta(meta) == spec
