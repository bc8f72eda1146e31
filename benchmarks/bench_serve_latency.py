"""Real-time serving: cold one-shot prediction vs amortized cached-state
prediction vs batch size, the kernel-implementation sweep (dense se_ard vs
se_ard_pallas cross-covariance vs the fused xcov_diag serving kernel), and
the routed/deadline serving path (core/api.py + launch/gp_serve.py).

What the paper's real-time claim cashes out to in this codebase:

* cold       — the legacy one-shot path (``ppitc.predict``): every call
  redoes the O((|D|/M)^3) local summaries and |S|^3 solves;
* fit        — one-time cost of building the cached ``PosteriorState``;
* amortized  — jitted ``predict_batch_diag`` over the cached state:
  O(|U||S| + |S|^2) per call, the per-query latency a serving deployment
  actually pays, swept over microbatch sizes;
* routed     — ``ppic.predict_routed_diag`` through a routed ``GPServer``:
  the batch-composition-invariant pPIC path (Remark 2);
* p99        — ticket latency under a low arrival rate, size-only trigger
  vs the deadline-driven flusher. Arrivals tick a virtual clock; real
  flush compute is folded in, so the comparison captures queueing delay
  plus actual predict cost.

Acceptance gates (asserted so `python -m benchmarks.run --only serve` fails
loudly on a regression):

* amortized repeated-query prediction >= 5x faster than the cold path at
  n=4096, M=8 (full size only), posteriors allclose to the legacy path;
* the deadline flusher's p99 ticket latency beats the size-only trigger at
  low arrival rates (every size);
* the fused xcov_diag path beats the dense se_ard serving path — on
  wall-clock (p50/p99 asserted not-worse) when a real accelerator backs the
  Pallas kernel, on the per-dispatch HBM/arithmetic-intensity model on
  CPU-only CI (interpret mode executes the kernel body in Python, so its
  wall time means nothing);
* the two-bucket routed scatter pads >= 2x fewer rows than the capacity-|U|
  layout at M=8 balanced traffic (deterministic, asserted everywhere); its
  p50/p99 ticket latency is asserted not-worse on accelerators only — the
  scheme trades (M+G)·cap computed rows for M+G dispatched programs, and
  XLA-CPU's batched triangular solve bills per PROGRAM almost independently
  of the RHS width, so the row saving only cashes out where the solve is
  column-scaled (TPU/GPU). Both latencies are emitted either way;
* plan_vs_legacy — the serving-plan backend cache
  (``ServeSpec(cached_cinv=True)``): the routed flush executable serving
  the per-block solve from precomputed C⁻¹ (one batched matmul) must BEAT
  the per-flush batched-trsm program on CPU — the cached-C⁻¹ design exists
  precisely because CPU trsm bills per program (asserted; same-g
  executables compared on the same padded batch, posteriors allclose).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts
from repro.core import api, covariance as cov, ppic, ppitc, support
from repro.data import synthetic
from repro.launch.gp_serve import GPServer
from repro.launch.mesh import make_mesh
from repro.parallel.runner import (ShardMapRunner, VmapRunner,
                                   routed_capacity)
from repro.serving import TenantScheduler

from benchmarks import common

N, M, S_SIZE = 4096, 8, 128
BATCHES = (1, 8, 64, 256)
SPEEDUP_GATE = 5.0
P99_SLACK = 1.25      # wall-clock not-worse gates tolerate CPU timer noise
# multi-tenant Zipf sim: sharing one runtime must not cost the LIGHTEST
# tenant more than these factors over being served alone on the identical
# arrival grid. The p50 gate is the tight one — head-of-line blocking or a
# cross-tenant recompile would shift the median by ~n_tenants x. The p99
# factor is deliberately loose: the sim charges real wall time to the
# virtual clock, and on a noisy shared-CPU box a single scheduling hiccup
# lands on whichever flush is in flight — with ~Zipf-tail sample counts the
# light tenant's p99 IS its max, so the p99 column guards against unbounded
# pathologies, not jitter.
N_TENANTS = 4
ZIPF_EXPONENT = 1.1
MEDIAN_ISOLATION_FACTOR = 2.0
TAIL_ISOLATION_FACTOR = 10.0


def run_impl_sweep(kfn, params, state, X_test, batches, tag: str):
    """dense se_ard vs se_ard_pallas xcov-only vs fused xcov_diag over the
    serving batch ladder, on one fitted state (VmapRunner / ShardMapRunner
    produce bitwise-identical states, so ``tag`` names the fit backend)."""
    on_tpu = jax.default_backend() == "tpu"
    pallas_impl = "pallas" if on_tpu else "pallas_interpret"
    spec_xcov = cov.make_spec("se", impl=pallas_impl, fused=False)
    spec_fused = cov.make_spec("se", impl=pallas_impl, fused=True)
    s = state.S.shape[0]
    d = X_test.shape[1]
    for u in batches:
        Uq = X_test[:u]
        fns = {
            "dense": jax.jit(lambda Uq=Uq: ppitc.predict_batch_diag(
                kfn, params, state, Uq)),
            "xcov": jax.jit(lambda Uq=Uq: ppitc.predict_batch_diag(
                spec_xcov, params, state, Uq)),
            "fused": jax.jit(lambda Uq=Uq: ppitc.predict_batch_diag(
                spec_fused, params, state, Uq)),
        }
        ref_m, ref_v = fns["dense"]()
        lat = {}
        for name, fn in fns.items():
            m, v = fn()
            assert jnp.allclose(m, ref_m, rtol=1e-4, atol=1e-5), \
                (tag, name, u, float(jnp.abs(m - ref_m).max()))
            assert jnp.allclose(v, ref_v, rtol=1e-3, atol=1e-5), \
                (tag, name, u, float(jnp.abs(v - ref_v).max()))
            samples = [common.timeit(lambda fn=fn: fn()[0], repeats=1,
                                     warmup=0) for _ in range(7)]
            lat[name] = {"p50": float(np.percentile(samples, 50)),
                         "p99": float(np.percentile(samples, 99))}
        hbm_d = common.xcov_hbm_bytes(u, s, d, fused=False)
        hbm_f = common.xcov_hbm_bytes(u, s, d, fused=True)
        common.emit(
            f"serve/xcov_sweep_{tag}/u{u}", lat["dense"]["p50"],
            f"xcov_p50={lat['xcov']['p50']:.0f};"
            f"fused_p50={lat['fused']['p50']:.0f};"
            f"fused_p99={lat['fused']['p99']:.0f};"
            f"dense_p99={lat['dense']['p99']:.0f};"
            f"hbm_dense={hbm_d};hbm_fused={hbm_f};"
            f"hbm_saving={hbm_d / hbm_f:.2f}x")
        # the falsifiable acceptance gate — fused beats dense on wall-clock
        # (p50/p99) — arms on a real accelerator. On CPU the Pallas body is
        # Python-interpreted, so wall-clock means nothing; the emitted
        # hbm_* model columns carry the claim there (they are a model of
        # the same kernel both backends run, not a measurable gate —
        # asserting model < model+const would be a tautology).
        if on_tpu:
            for q in ("p50", "p99"):
                assert lat["fused"][q] <= lat["dense"][q] * P99_SLACK, \
                    f"{tag} u={u}: fused {q} {lat['fused'][q]:.0f}us worse " \
                    f"than dense {lat['dense'][q]:.0f}us on TPU"
    common.metric(f"xcov_hbm_saving_{tag}",
                  common.xcov_hbm_bytes(batches[-1], s, d, fused=False)
                  / common.xcov_hbm_bytes(batches[-1], s, d, fused=True))


def ticket_latency_ms(model, U, *, n_req: int, interarrival_ms: float,
                      max_batch: int, deadline_ms: float | None,
                      routed: bool = False) -> dict[str, float]:
    """Simulated serving loop: one request every ``interarrival_ms`` on a
    virtual clock, ``pump()`` between arrivals. Each step ``sync()``s the
    server before advancing the clock by the real elapsed time, so flush
    dispatch AND device compute are both charged to ticket latency (flushes
    are async — without the barrier only host dispatch would be measured).
    Returns per-ticket latency percentiles {"p50": ms, "p99": ms}."""
    t = [0.0]
    srv = GPServer(model, max_batch=max_batch, flush_deadline_ms=deadline_ms,
                   routed=routed, clock=lambda: t[0])
    # steady-state measurement: pre-compile every executable the sim can
    # hit — all buckets AND, for routed plans, the whole overflow-group
    # ladder — so one-time XLA compilation doesn't masquerade as queueing
    # latency (a mid-sim compile lands on one unlucky flush and owns p99)
    srv.plan.warmup(U.shape[1], dtype=np.asarray(U).dtype)
    submit_at: dict[int, float] = {}
    done_at: dict[int, float] = {}

    def harvest():
        for tk in list(submit_at):
            if tk not in done_at and srv.done(tk):
                done_at[tk] = t[0]

    def step(fn):
        """Run one serving action, charge its real wall time (including
        materializing any flushed results) to the virtual clock, then stamp
        newly-finished tickets at the post-compute clock."""
        w0 = time.perf_counter()
        out = fn()
        srv.sync()
        t[0] += time.perf_counter() - w0
        harvest()
        return out

    # GC is quiesced for the measured loop: a gen-2 collection walks the
    # whole benchmark harness's heap (~hundreds of ms here) and lands on
    # whichever flush is unlucky — pure measurement noise that swamps the
    # p99 the sim exists to compare. Collect up front, then hold.
    gc.collect()
    gc.disable()
    try:
        for i in range(n_req):
            t_arrival = t[0]                   # before any flush compute
            tk = step(lambda: srv.submit(U[i % U.shape[0]]))
            submit_at[tk] = t_arrival
            step(srv.pump)
            t[0] += interarrival_ms * 1e-3
            step(srv.pump)
        step(srv.flush)                        # drain the tail
    finally:
        gc.enable()
    lats = [(done_at[tk] - submit_at[tk]) * 1e3 for tk in submit_at]
    return {"p50": float(np.percentile(lats, 50)),
            "p99": float(np.percentile(lats, 99))}


def zipf_draws(n_req: int, n_tenants: int, seed: int = 0) -> np.ndarray:
    """Deterministic Zipf-distributed tenant index per request: tenant 0 is
    the heavy hitter, tenant n-1 the lightest (p ~ 1/(k+1)^exponent)."""
    p = 1.0 / np.arange(1, n_tenants + 1) ** ZIPF_EXPONENT
    p /= p.sum()
    return np.random.RandomState(seed).choice(n_tenants, size=n_req, p=p)


def multi_tenant_latency_ms(model, U, draws, *, n_tenants: int,
                            interarrival_ms: float, max_batch: int,
                            deadline_ms: float,
                            only: int | None = None) -> dict:
    """Zipf-multiplexed serving sim on the shared virtual clock.

    Each of ``draws``' entries is one arrival slot of ``interarrival_ms``;
    the drawn tenant submits, then the central ``pump()`` runs — the same
    step/sync/harvest protocol as ``ticket_latency_ms``, so real flush
    compute (everyone's, which is the point) is charged to ticket latency.
    All tenants serve the same fitted model, so they land in ONE compiled
    lineage — ``n_lineages``/``recompiles`` in the return value are the
    probe counters ``run()`` asserts on.

    ``only=k`` replays the SAME global grid but admits and submits only
    tenant k — the isolated baseline: identical arrival times and pump
    cadence, zero cross-tenant interference. Returns per-tenant latency
    percentiles plus the shared-lineage probe counters."""
    t = [0.0]
    sched = TenantScheduler(clock=lambda: t[0])
    tenants = list(range(n_tenants)) if only is None else [only]
    spec = api.ServeSpec(max_batch=max_batch, routed=True)
    for k in tenants:
        sched.admit(f"t{k}", model, spec, flush_deadline_ms=deadline_ms)
    # one warmup covers every tenant: plan-compatible tenants share a
    # single compiled lineage, which is exactly what the probe asserts
    plan = sched.registry.get(f"t{min(tenants)}").plan
    plan.warmup(U.shape[1], dtype=np.asarray(U).dtype)
    # prime one full submit->flush->result round per tenant: warmup covers
    # XLA compiles, this covers everything else that is slow exactly once
    # (dispatch caches, allocator growth) — a one-off spike charged to the
    # virtual clock would otherwise own somebody's p99
    for k in tenants:
        tk0 = sched.submit(f"t{k}", U[0])
        sched.result(f"t{k}", tk0)
    t[0] = 0.0
    traces0 = plan.stats.n_traces
    submit_at: dict[tuple, float] = {}
    done_at: dict[tuple, float] = {}

    def harvest():
        for tid, tk in list(submit_at):
            if (tid, tk) not in done_at and sched.done(tid, tk):
                done_at[(tid, tk)] = t[0]
                sched.result(tid, tk)   # collect: keeps sync() off resolved
                # tickets, as a real client loop would

    def step(fn):
        w0 = time.perf_counter()
        out = fn()
        sched.sync()
        t[0] += time.perf_counter() - w0
        harvest()
        return out

    gc.collect()
    gc.disable()
    try:
        for i, k in enumerate(draws):
            if only is None or int(k) == only:
                tid = f"t{int(k)}"
                t_arrival = t[0]
                tk = step(lambda: sched.submit(tid, U[i % U.shape[0]]))
                submit_at[(tid, tk)] = t_arrival
            step(sched.pump)
            t[0] += interarrival_ms * 1e-3
            step(sched.pump)
        step(sched.flush)                      # drain every tail
    finally:
        gc.enable()
    out = {}
    for k in tenants:
        tid = f"t{k}"
        lats = [(done_at[key] - at) * 1e3 for key, at in submit_at.items()
                if key[0] == tid]
        out[tid] = {"p50": float(np.percentile(lats, 50)),
                    "p99": float(np.percentile(lats, 99)),
                    "n": len(lats)}
    return {"tenants": out, "n_lineages": sched.registry.n_lineages,
            "recompiles": plan.stats.n_traces - traces0,
            "rollup": sched.rollup()}


def run(quick: bool = False, smoke: bool = False):
    n = 512 if smoke else (2048 if quick else N)
    s_size = 32 if smoke else S_SIZE
    batches = (1, 8) if smoke else BATCHES
    key = jax.random.PRNGKey(0)
    ds = synthetic.standardize(synthetic.aimpeak_like(key, n=n, n_test=256))
    kfn = cov.make_kernel("se")
    params = cov.init_params(ds.X.shape[1], signal=1.0, noise=0.3,
                             lengthscale=1.2, dtype=jnp.float32)
    S = support.select_support(kfn, params, ds.X[:min(n, 2048)], s_size)
    runner = VmapRunner(M=M)
    Uq = ds.X_test[:64]

    # --- cold path: one-shot predict redoes the whole fit per call --------
    cold_fn = jax.jit(lambda: ppitc.predict(kfn, params, S, ds.X, ds.y, Uq,
                                            runner).mean)
    t_cold = common.timeit(cold_fn)
    common.emit(f"serve/cold_fit_predict/n{n}", t_cold, f"u={Uq.shape[0]}")

    # --- fit once, cache the state -----------------------------------------
    fit_fn = jax.jit(lambda: ppitc.fit(kfn, params, ds.X, ds.y, S=S,
                                       runner=runner))
    t_fit = common.timeit(lambda: jax.tree.leaves(fit_fn())[0])
    common.emit(f"serve/fit_once/n{n}", t_fit, "state build (amortized away)")
    state = fit_fn()

    # --- amortized path: jitted predict over the cached state --------------
    predict_fn = jax.jit(partial(ppitc.predict_batch_diag, kfn))
    t_amort = common.timeit(lambda: predict_fn(params, state, Uq)[0])
    speedup = t_cold / max(t_amort, 1e-9)
    common.emit(f"serve/amortized/n{n}", t_amort,
                f"u={Uq.shape[0]};speedup={speedup:.1f}x")
    common.metric("amortized_speedup", speedup)
    common.metric("amortized_us_per_query", t_amort / Uq.shape[0])

    # --- correctness: cached path matches the legacy one-shot posterior ----
    # float32 perf-path sanity (atol floor = fp32 accumulation noise) ...
    legacy = ppitc.predict(kfn, params, S, ds.X, ds.y, Uq, runner)
    mean, var = predict_fn(params, state, Uq)
    assert jnp.allclose(mean, legacy.mean, rtol=1e-5, atol=1e-5), \
        float(jnp.abs(mean - legacy.mean).max())
    assert jnp.allclose(var, legacy.var, rtol=1e-4, atol=1e-5), \
        float(jnp.abs(var - legacy.var).max())
    # ... and the strict rtol=1e-5 gate where it is meaningful: float64
    with jax.experimental.enable_x64():
        f64 = lambda a: jnp.asarray(a, jnp.float64)
        p64 = jax.tree.map(f64, params)
        X64, y64, S64, U64 = map(f64, (ds.X, ds.y, S, Uq))
        legacy64 = ppitc.predict(kfn, p64, S64, X64, y64, U64, runner)
        st64 = ppitc.fit(kfn, p64, X64, y64, S=S64, runner=runner)
        m64, v64 = ppitc.predict_batch_diag(kfn, p64, st64, U64)
        assert jnp.allclose(m64, legacy64.mean, rtol=1e-5), \
            float(jnp.abs(m64 - legacy64.mean).max())
        assert jnp.allclose(v64, legacy64.var, rtol=1e-5), \
            float(jnp.abs(v64 - legacy64.var).max())

    if not (quick or smoke):
        assert speedup >= SPEEDUP_GATE, \
            f"amortized speedup {speedup:.1f}x < {SPEEDUP_GATE}x gate"

    # --- per-query latency vs microbatch size (through the server) ---------
    model = api.FittedGP(api.get("ppitc"), kfn, params, state)
    srv = GPServer(model, max_batch=max(batches))
    for u in batches:
        Ub = ds.X_test[:u]
        t = common.timeit(lambda: srv.predict(Ub)[0])
        common.emit(f"serve/batch{u}/n{n}", t,
                    f"per_query_us={t / u:.1f}")

    # --- kernel-impl sweep: dense vs pallas xcov vs fused, both runners ----
    run_impl_sweep(kfn, params, state, ds.X_test, batches, "vmap")
    if jax.device_count() > 1:
        mesh = make_mesh((jax.device_count(),), ("data",))
        sm_runner = ShardMapRunner(mesh=mesh, axis_name="data")
        if n % sm_runner.num_machines == 0:
            state_sm = ppitc.fit(kfn, params, ds.X, ds.y, S=S,
                                 runner=sm_runner)
            run_impl_sweep(kfn, params, state_sm, ds.X_test, batches,
                           "shardmap")
    else:
        # a 1-device mesh would time the vmap path under a shard_map label —
        # a row that LOOKS like cross-device evidence but isn't. Say so
        # explicitly instead of silently emitting misleading numbers (the
        # CPU-CI case).
        common.emit("serve/xcov_sweep_shardmap", 0.0,
                    "skipped: single-device mesh")

    # --- routed pPIC serving: composition-invariant, centroid-dispatched ---
    pic_state = ppic.fit(kfn, params, ds.X, ds.y, S=S, runner=runner)
    pic_model = api.FittedGP(api.get("ppic"), kfn, params, pic_state)
    srv_routed = GPServer(pic_model, max_batch=max(batches), routed=True)
    u_r = min(48, ds.X_test.shape[0])
    Ur = ds.X_test[:u_r]
    t_routed = common.timeit(lambda: srv_routed.predict(Ur)[0])
    pos_fn = jax.jit(partial(ppic.predict_batch_diag, kfn))
    t_pos = common.timeit(lambda: pos_fn(params, pic_state, Ur)[0])
    common.emit(f"serve/routed{u_r}/n{n}", t_routed,
                f"positional_us={t_pos:.1f}")
    # routed-through-server == direct routed call (bucket padding is inert)
    m_r, v_r = srv_routed.predict(Ur)
    ref_m, ref_v = ppic.predict_routed_diag(kfn, params, pic_state, Ur)
    assert jnp.allclose(m_r, ref_m, rtol=1e-5, atol=1e-5), \
        float(jnp.abs(m_r - ref_m).max())
    assert jnp.allclose(v_r, ref_v, rtol=1e-4, atol=1e-5), \
        float(jnp.abs(v_r - ref_v).max())
    # composition invariance at bench scale: a permuted batch permutes output
    perm = np.random.RandomState(0).permutation(u_r)
    m_p, _ = ppic.predict_routed_diag(kfn, params, pic_state, Ur[perm])
    np.testing.assert_array_equal(np.asarray(m_p), np.asarray(ref_m)[perm])

    # --- two-bucket routed scatter vs the capacity-|U| layout --------------
    # padded-rows reduction is deterministic: (M + G)·cap vs M·|U| computed
    # rows for the same batch (the >= 2x gate at M=8 balanced traffic)
    cap, G = routed_capacity(u_r, M)
    rows_two = (M + G) * cap
    rows_full = M * u_r
    common.metric("routed_padded_rows_ratio", rows_full / rows_two)
    common.emit(f"serve/routed_two_bucket/u{u_r}", 0.0,
                f"rows_two_bucket={rows_two};rows_capacity={rows_full};"
                f"reduction={rows_full / rows_two:.2f}x")
    assert rows_full / rows_two >= 2.0, \
        f"two-bucket scatter reduces padded rows only " \
        f"{rows_full / rows_two:.2f}x at M={M}"
    # posterior equality (bitwise) + wall-clock not-worse on the direct call
    cap_m, cap_v = ppic.predict_routed_diag_capacity(kfn, params, pic_state,
                                                     Ur)
    np.testing.assert_array_equal(np.asarray(ref_m), np.asarray(cap_m))
    np.testing.assert_array_equal(np.asarray(ref_v), np.asarray(cap_v))
    cap_fn = jax.jit(partial(ppic.predict_routed_diag_capacity, kfn))
    t_cap = common.timeit(lambda: cap_fn(params, pic_state, Ur)[0])
    common.emit(f"serve/routed_capacity{u_r}/n{n}", t_cap,
                f"two_bucket_us={t_routed:.1f}")

    # --- serving-plan backend cache: cached C^{-1} vs per-flush trsm -------
    # The plan/execute split's headline backend cache (ServeSpec
    # cached_cinv): the routed flush's per-block solve becomes one batched
    # matmul against precomputed (C_L C_L^T)^{-1}. Compared at the
    # EXECUTABLE level — same overflow-group program g, same padded batch —
    # so the claim isolates trsm-vs-matmul, not host staging. CPU is where
    # this pays (batched trsm bills per program there), hence the gate is
    # asserted on CPU; it holds a fortiori where solves are column-scaled.
    spec_t = api.ServeSpec(routed=True, max_batch=max(batches))
    spec_c = dataclasses.replace(spec_t, cached_cinv=True)
    plan_t = pic_model.plan(spec_t)
    plan_c = pic_model.plan(spec_c)
    m_t, v_t = plan_t.routed_diag(Ur)
    m_c, v_c = plan_c.routed_diag(Ur)
    assert jnp.allclose(m_c, m_t, rtol=1e-3, atol=1e-3), \
        float(jnp.abs(m_c - m_t).max())
    assert jnp.allclose(v_c, v_t, rtol=1e-3, atol=1e-3), \
        float(jnp.abs(v_c - v_t).max())
    bucket = plan_t.bucket_for(u_r)
    Upad = np.zeros((bucket, Ur.shape[1]), np.asarray(Ur).dtype)
    Upad[:u_r] = np.asarray(Ur)
    # the plan's own routing decision: the timed program must be provisioned
    # exactly as a real flush's (pad rows packed into spare main capacity)
    assign, g = plan_t._route(Upad, u_r)
    ex_t, ex_c = plan_t._routed_exec(g), plan_c._routed_exec(g)
    t_trsm = np.median([common.timeit(
        lambda: ex_t(params, pic_state, None, Upad, assign)[0],
        repeats=20, warmup=2) for _ in range(5)])
    t_cinv = np.median([common.timeit(
        lambda: ex_c(params, pic_state, plan_c.caches, Upad, assign)[0],
        repeats=20, warmup=2) for _ in range(5)])
    common.emit(f"serve/plan_vs_legacy/u{u_r}", t_cinv,
                f"trsm_us={t_trsm:.1f};g={g};"
                f"speedup={t_trsm / max(t_cinv, 1e-9):.2f}x")
    common.metric("plan_cinv_speedup", t_trsm / max(t_cinv, 1e-9))
    if jax.default_backend() == "cpu":
        assert t_cinv <= t_trsm, \
            (f"cached-C^-1 routed flush {t_cinv:.0f}us not faster than the "
             f"trsm path {t_trsm:.0f}us on CPU (g={g})")

    # --- compiled-program contract audit: the zero-recompile claim, struct-
    # urally — every executable the routed serving drive touches must
    # fingerprint (jaxpr sha256) identical across >= 3 rebind generations,
    # with zero new traces (repro.analysis.contracts; full two-tenant
    # interleaving audit runs in the CI chaos job)
    audit = contracts.audit_rebind_generations(
        plan_c, lambda pl: (pl.diag(Ur), pl.routed_diag(Ur)),
        n_generations=3)
    audit_ok = (audit["rebind_identical"]
                and audit["rebind_new_traces"] == 0)
    common.emit(f"serve/contract_audit/u{u_r}", 0.0,
                f"n_executables={audit['n_executables']};"
                f"generations={audit['n_rebind_generations']};"
                f"identical={audit_ok}")
    common.metric("audit_n_executables", float(audit["n_executables"]))
    common.metric("audit_rebind_generations",
                  float(audit["n_rebind_generations"]))
    common.metric("audit_identical", float(audit_ok))
    assert audit_ok, \
        (f"contract audit: rebind generations not fingerprint-identical "
         f"(identical={audit['rebind_identical']}, "
         f"new_traces={audit['rebind_new_traces']})")

    # --- deadline flusher vs size-only trigger: p50/p99 at low arrival rate
    # max_batch=64 + 2ms interarrival: the size trigger alone would hold the
    # oldest ticket ~126ms; a 20ms deadline caps that regardless of traffic
    n_req = 96 if smoke else 256
    sim = dict(n_req=n_req, interarrival_ms=2.0, max_batch=64, routed=True)
    lat_size = ticket_latency_ms(pic_model, Ur, deadline_ms=None, **sim)
    lat_dead = ticket_latency_ms(pic_model, Ur, deadline_ms=20.0, **sim)
    common.emit(f"serve/p99_size_only/n{n}", lat_size["p99"] * 1e3,
                f"p50_ms={lat_size['p50']:.1f};p99_ms={lat_size['p99']:.1f}")
    common.emit(f"serve/p99_deadline20/n{n}", lat_dead["p99"] * 1e3,
                f"p50_ms={lat_dead['p50']:.1f};p99_ms={lat_dead['p99']:.1f}")
    for trig, lat in (("size_only", lat_size), ("deadline20", lat_dead)):
        common.metric(f"serve_p50_ms_{trig}", lat["p50"])
        common.metric(f"serve_p99_ms_{trig}", lat["p99"])
    assert lat_dead["p99"] < lat_size["p99"], \
        (f"deadline flusher p99 {lat_dead['p99']:.1f}ms not below size-only "
         f"trigger p99 {lat_size['p99']:.1f}ms at low arrival rate")

    # --- two-bucket vs capacity-|U| under the same deadline traffic --------
    # same simulated arrivals against a server whose routed predict runs the
    # old capacity layout. The wall-clock not-worse gate applies on real
    # accelerators only: XLA-CPU's batched triangular solve bills per
    # dispatched program (M+G for two-bucket vs M) almost independently of
    # the RHS width, so the ~(alpha+1)/M row reduction — asserted
    # deterministically above — does not cash out on CPU wall-clock.
    cap_method = dataclasses.replace(
        api.get("ppic"), plan_fn=None,   # generic plan jits the raw impl
        predict_routed_diag_fn=lambda k, p, s, U, tile=None:
            ppic.predict_routed_diag_capacity(k, p, s, U))
    cap_model = api.FittedGP(cap_method, kfn, params, pic_state)
    lat_cap = ticket_latency_ms(cap_model, Ur, deadline_ms=20.0, **sim)
    common.emit(f"serve/p99_capacity_layout/n{n}", lat_cap["p99"] * 1e3,
                f"p50_ms={lat_cap['p50']:.1f};p99_ms={lat_cap['p99']:.1f}")
    for trig, lat in (("capacity20", lat_cap),):
        common.metric(f"serve_p50_ms_{trig}", lat["p50"])
        common.metric(f"serve_p99_ms_{trig}", lat["p99"])
    if jax.default_backend() == "tpu":
        assert lat_dead["p50"] <= lat_cap["p50"] * P99_SLACK, \
            (f"two-bucket routed p50 {lat_dead['p50']:.1f}ms worse than "
             f"capacity layout {lat_cap['p50']:.1f}ms")
        assert lat_dead["p99"] <= lat_cap["p99"] * P99_SLACK, \
            (f"two-bucket routed p99 {lat_dead['p99']:.1f}ms worse than "
             f"capacity layout {lat_cap['p99']:.1f}ms")

    # --- multi-tenant Zipf sim: tail isolation under skewed sharing --------
    # N_TENANTS tenants on one TenantScheduler, Zipf-skewed arrivals (tenant
    # 0 is the heavy hitter, tenant N-1 the lightest). Three asserted claims:
    # every tenant shares ONE compiled lineage, the measured loop triggers
    # zero recompiles, and multiplexing must not cost the lightest tenant
    # more than TAIL_ISOLATION_FACTOR x its p99 when served alone on the
    # identical arrival/pump grid.
    draws = zipf_draws(n_req, N_TENANTS)
    light = N_TENANTS - 1
    mt_sim = dict(n_tenants=N_TENANTS, interarrival_ms=2.0, max_batch=64,
                  deadline_ms=20.0)
    mux = multi_tenant_latency_ms(pic_model, Ur, draws, **mt_sim)
    iso = multi_tenant_latency_ms(pic_model, Ur, draws, only=light, **mt_sim)
    lat_hv, lat_lt = mux["tenants"]["t0"], mux["tenants"][f"t{light}"]
    lat_iso = iso["tenants"][f"t{light}"]
    assert sum(v["n"] for v in mux["tenants"].values()) == n_req
    assert lat_lt["n"] == lat_iso["n"]
    common.emit(f"serve/mt_zipf{N_TENANTS}/n{n}", lat_lt["p99"] * 1e3,
                f"light_p50_ms={lat_lt['p50']:.1f};"
                f"light_p99_ms={lat_lt['p99']:.1f};"
                f"heavy_p99_ms={lat_hv['p99']:.1f};"
                f"iso_p99_ms={lat_iso['p99']:.1f};"
                f"n_light={lat_lt['n']};lineages={mux['n_lineages']}")
    common.metric("mt_heavy_p50_ms", lat_hv["p50"])
    common.metric("mt_heavy_p99_ms", lat_hv["p99"])
    common.metric("mt_light_p50_ms", lat_lt["p50"])
    common.metric("mt_light_p99_ms", lat_lt["p99"])
    common.metric("mt_light_iso_p50_ms", lat_iso["p50"])
    common.metric("mt_light_iso_p99_ms", lat_iso["p99"])
    common.metric("mt_median_isolation",
                  lat_lt["p50"] / max(lat_iso["p50"], 1e-9))
    common.metric("mt_tail_isolation",
                  lat_lt["p99"] / max(lat_iso["p99"], 1e-9))
    common.metric("mt_lineages", mux["n_lineages"])
    common.metric("mt_recompiles", mux["recompiles"])
    assert mux["n_lineages"] == 1, \
        f"{N_TENANTS} plan-compatible tenants forked {mux['n_lineages']} " \
        f"compiled lineages (expected 1)"
    assert mux["recompiles"] == 0, \
        f"multi-tenant loop triggered {mux['recompiles']} recompiles " \
        f"after warmup (tenant interleaving must not retrace)"
    assert lat_lt["p50"] <= lat_iso["p50"] * MEDIAN_ISOLATION_FACTOR, \
        (f"light tenant p50 {lat_lt['p50']:.1f}ms under Zipf multiplexing "
         f"exceeds {MEDIAN_ISOLATION_FACTOR}x its isolated p50 "
         f"{lat_iso['p50']:.1f}ms — head-of-line blocking")
    assert lat_lt["p99"] <= lat_iso["p99"] * TAIL_ISOLATION_FACTOR, \
        (f"light tenant p99 {lat_lt['p99']:.1f}ms under Zipf multiplexing "
         f"exceeds {TAIL_ISOLATION_FACTOR}x its isolated p99 "
         f"{lat_iso['p99']:.1f}ms — tail isolation broken")
    totals = mux["rollup"]["totals"]
    assert totals["n_rejected"] == 0 and totals["n_shed"] == 0

    return speedup


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
