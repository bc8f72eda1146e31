#!/usr/bin/env python3
"""Bring-up smoke run of the GP fit -> state -> ServePlan -> TenantScheduler
path on TPU, at the paper's fixed point.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the sharded fit on four chips

One chip: the aimpeak point of ``configs.gp_experiments.PAPER_GRID``
(|D| = 32,000 in M = 20 co-clustered blocks, |S| = 2,048, d = 5, float32),
fitted through ``api.fit`` with the compiled Pallas kernels, served through
a ``TenantScheduler`` (ppitc diag and centroid-routed ppic), one assimilated
wave hot-swapped through the ``StateStore``, one fused ``xcov_diag`` batch at
|S| = 1,024 and ``block_q`` = 64, and a routed ppic check against the dense
PIC oracle at a small |D|.

Four chips: ppitc and ppic fitted at |D| = 32,000 with M = 4 machines (a
point of the grid's machine sweep), one per device, through
``ShardMapRunner``, compared leaf by leaf and on a served batch with the
single-device ``VmapRunner`` fit; every per-block state array must sit one
block per device. Nothing else runs in that mode.

Every served result is compared with a plain float32 jnp reference written
here, not with the code under test. Each phase prints one line: wall time
after ``block_until_ready``, peak device memory, the path taken, the
compile-cache hits and misses, and its check with its tolerance. A failed
check, or a platform other than TPU, exits nonzero and prints no result; the
last line of a passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

IMPL = "pallas"     # the kernels' implementation, stated (never "auto")

# float32 tolerances, as max |served - reference| / max |reference|. Both
# sides run f32 Cholesky pipelines over |S| = 2,048 support points, whose
# round-off reaches ~1e-4 of the output scale; a bf16-pass matmul anywhere in
# the library shows as ~1e-2 errors or non-finite values.
TOL = 2e-3
# the weights w compared through one shared factor (``state_leaf_check``):
# ~4e-6 between the runners on the CPU at |D| = 8,000, |S| = 1,024
W_TOL = 1e-4
# the dense oracle factors a |D| x |D| matrix in f32 where the summary path
# factors |S|- and b-sized ones; the variance is a small difference of large
# terms there
ORACLE_TOL_MEAN, ORACLE_TOL_VAR = 1e-3, 1e-2
REF_PRECISION = "highest"   # the reference's own matmul precision
D = 5                       # aimpeak input dimension


@dataclasses.dataclass(frozen=True)
class Sizes:
    n: int                   # |D|
    machines: int            # M blocks on one chip (VmapRunner)
    support: int             # |S|
    four_machines: int       # M on four chips, one block per device
    n_queries: int = 384     # served through the scheduler per phase
    max_batch: int = 64      # scheduler flush size
    wave: int = 400          # assimilated rows (M | wave)
    fused_support: int = 1024
    fused_block_q: int = 64
    fused_batch: int = 256
    oracle_n: int = 4000     # |D| of the dense PIC oracle check
    oracle_support: int = 512


def paper_sizes() -> Sizes:
    from repro.configs.gp_experiments import PAPER_GRID
    g = PAPER_GRID["aimpeak"]
    assert g.input_dim == D
    return Sizes(n=g.fixed_data, machines=g.fixed_machines,
                 support=g.fixed_param, four_machines=g.machines[0])


class Smoke:
    """Phase lines: wall time, peak memory, path, compile-cache deltas and
    checks. Failed checks are collected; exceptions propagate."""

    def __init__(self):
        import jax
        from repro.launch import compile_cache
        self.jax = jax
        self.cache = compile_cache.CacheCounter()
        self.failed: list[str] = []

    def _peak(self) -> str:
        stats = self.jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return "not reported" if peak is None else f"{peak} B"

    def phase(self, name: str, fn, *, path="-", check=None):
        """Run ``fn`` to completion and print its line. ``path`` is a string
        or a callable read after the run; ``check`` maps the outputs to
        ``(ok, description)``."""
        req, hits = self.cache.requests, self.cache.hits
        t0 = time.perf_counter()
        out = self.jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        d_req, d_hit = self.cache.requests - req, self.cache.hits - hits
        path = path() if callable(path) else path
        line = (f"[phase] {name}: wall {wall:.6f} s; peak device memory "
                f"{self._peak()}; path {path}; compile cache {d_hit} hits "
                f"{d_req - d_hit} misses")
        if check is not None:
            ok, desc = check(out)
            line += f"; check {desc} {'ok' if ok else 'FAIL'}"
            if not ok:
                self.failed.append(name)
        print(line, flush=True)
        return out

    def note(self, name: str, ok: bool, desc: str) -> None:
        print(f"[check] {name}: {desc} {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)


def close(what: str, got, ref, tol: float):
    """(ok, text): all finite and max |got - ref| / max |ref| <= tol."""
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return False, f"{what} shape {got.shape} vs {ref.shape}"
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return False, f"{what} not finite"
    err = float(np.max(np.abs(got - ref))) if got.size else 0.0
    rel = err / max(float(np.max(np.abs(ref))) if ref.size else 0.0, 1e-30)
    return rel <= tol, f"{what} max|err| {err:.3e} rel {rel:.3e} (tol {tol:g})"


def both(*checks):
    return all(ok for ok, _ in checks), "; ".join(d for _, d in checks)


def mean_var_check(ref_mean, ref_var, tol=TOL):
    return lambda out: both(close("mean", out[0], ref_mean, tol),
                            close("var", out[1], ref_var, tol))


# ---------------------------------------------------------------------------
# Plain float32 jnp reference, independent of the code under test.
# ---------------------------------------------------------------------------

def _ref_precision(fn):
    @functools.wraps(fn)
    def pinned(*args, **kwargs):
        import jax
        with jax.default_matmul_precision(REF_PRECISION):
            return fn(*args, **kwargs)
    return pinned


def _ref_kernel(params, A, B):
    import jax.numpy as jnp
    ls = jnp.exp(params["log_lengthscale"])
    A, B = A / ls, B / ls
    d2 = (jnp.sum(A * A, 1)[:, None] + jnp.sum(B * B, 1)[None, :]
          - 2.0 * A @ B.T)
    return jnp.exp(2.0 * params["log_signal"]) * jnp.exp(
        -0.5 * jnp.maximum(d2, 0.0))


def _chol(K, scale=None):
    """Cholesky with a 1e-6 relative f32 jitter (of ``scale``, default the
    mean diagonal), the regularisation the method defines."""
    import jax.numpy as jnp
    scale = jnp.mean(jnp.diag(K)) if scale is None else scale
    return jnp.linalg.cholesky(
        K + 1e-6 * scale * jnp.eye(K.shape[0], dtype=K.dtype))


@_ref_precision
def reference_summaries(params, S, blocks):
    """Eqs. (3)-(6) from raw data, whitened by L = chol K_SS: per block
    V = L⁻¹K_SD, C_L = chol Σ_{DD|S}, G = V C_L⁻ᵀ (so Σ̇ = L G Gᵀ Lᵀ), and
    globally A = I + Σ G Gᵀ = L⁻¹ Σ̈ L⁻ᵀ and c = L⁻¹ ÿ, summed over every
    (Xb, yb) in ``blocks`` (the fitted data, then any assimilated waves).
    Σ̈ itself is too ill-conditioned for a float32 Cholesky at this scale;
    A's eigenvalues are >= 1. Per-block factors are kept for the first
    entry."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    k = lambda A, B: _ref_kernel(params, A, B)
    noise = jnp.exp(2.0 * params["log_noise"])
    L = _chol(k(S, S))

    def block(a):
        Xm, ym = a
        V = solve_triangular(L, k(S, Xm), lower=True)
        C_L = _chol(k(Xm, Xm) + noise * jnp.eye(Xm.shape[0]) - V.T @ V)
        G = solve_triangular(C_L, V.T, lower=True).T          # (s, b)
        e = solve_triangular(C_L, ym, lower=True)             # C_L⁻¹ y
        Wy = solve_triangular(C_L, e, lower=True, trans=1)    # C⁻¹ y
        return V, C_L, Wy, G @ G.T, G @ e

    per = [jax.lax.map(block, (Xb, yb)) for Xb, yb in blocks]
    A = jnp.eye(S.shape[0]) + sum(jnp.sum(p[3], 0) for p in per)
    V, C_L, Wy = per[0][:3]
    return dict(L=L, A_L=jnp.linalg.cholesky(A),
                c=sum(jnp.sum(p[4], 0) for p in per), V=V, C_L=C_L, Wy=Wy)


def _global_terms(ref, W):
    """For whitened columns W = L⁻¹ X (s, u): (A_L⁻¹ W, A_L⁻¹ c), whose
    column dot products are Xᵀ Σ̈⁻¹ ÿ and whose squared norms are
    Xᵀ Σ̈⁻¹ X."""
    from jax.scipy.linalg import solve_triangular
    return (solve_triangular(ref["A_L"], W, lower=True),
            solve_triangular(ref["A_L"], ref["c"], lower=True))


@_ref_precision
def reference_pitc(params, S, ref, U):
    """Eqs. (7)-(8), diagonal."""
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    v = solve_triangular(ref["L"], _ref_kernel(params, S, U), lower=True)
    z, a = _global_terms(ref, v)
    return (z.T @ a, jnp.exp(2.0 * params["log_signal"])
            - jnp.sum(v * v, 0) + jnp.sum(z * z, 0))


@_ref_precision
def reference_pic(params, S, Xb, ref, U, assign):
    """Eqs. (12)-(14), diagonal, query i answered by block ``assign[i]``:
    every query is evaluated against every block, then row i is taken from
    its own block. With R = Σ_{U D|S} = K_UD - vᵀV and P = R C⁻¹:
    Φ = K_US - P K_DS, so L⁻¹Φᵀ = v - V Pᵀ; mean = Φ Σ̈⁻¹ÿ + P y;
    var = k - |v|² - rowsum(R∘P) + |A_L⁻¹ L⁻¹ Φᵀ|²."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_solve, solve_triangular
    v = solve_triangular(ref["L"], _ref_kernel(params, S, U), lower=True)
    sig2 = jnp.exp(2.0 * params["log_signal"])

    def block(a):
        Xm, V, C_L, Wy = a
        R = _ref_kernel(params, U, Xm) - v.T @ V               # (u, b)
        P = cho_solve((C_L, True), R.T).T                      # R C⁻¹
        z, c = _global_terms(ref, v - V @ P.T)
        return (z.T @ c + R @ Wy,
                sig2 - jnp.sum(v * v, 0) - jnp.sum(R * P, 1)
                + jnp.sum(z * z, 0))

    means, vars_ = jax.lax.map(block, (Xb, ref["V"], ref["C_L"], ref["Wy"]))
    rows = jnp.arange(U.shape[0])
    return means[assign, rows], vars_[assign, rows]


def nearest_block(U, Xb):
    """Each query's block: nearest block mean, in float64 NumPy."""
    import numpy as np
    cent = np.asarray(Xb, np.float64).mean(axis=1)
    U = np.asarray(U, np.float64)
    return np.argmin(((U[:, None, :] - cent[None]) ** 2).sum(-1), axis=1)


# ---------------------------------------------------------------------------
# Shared set-up.
# ---------------------------------------------------------------------------

def make_data(seed: int, n: int, machines: int, n_extra: int,
              n_queries: int):
    """aimpeak-shaped data co-clustered into M blocks (Remark 2): (X, y) in
    block order, (X_extra, y_extra) rows for assimilation, queries U."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import clustering
    from repro.data import synthetic
    k_data, k_clu = jax.random.split(jax.random.PRNGKey(seed))
    ds = synthetic.standardize(synthetic.aimpeak_like(
        k_data, n=n + n_extra, n_test=n_queries))
    X, y = np.asarray(ds.X[:n]), np.asarray(ds.y[:n])
    Xc, yc, _, _, _ = clustering.cocluster(X, y, X[:machines], machines,
                                           k_clu)
    return (jnp.asarray(Xc), jnp.asarray(yc), ds.X[n:], ds.y[n:],
            ds.X_test)


def gp_params():
    """The data generator's own hyperparameters (``aimpeak_like``)."""
    from repro.core import covariance as cov
    return cov.init_params(D, signal=1.0, noise=0.3, lengthscale=1.2)


def serve_through_scheduler(sched, tenant: str, U):
    """Submit every row, flush the tail, collect: the client-side path."""
    import numpy as np
    Un = np.asarray(U)
    tickets = [sched.submit(tenant, Un[i]) for i in range(Un.shape[0])]
    sched.flush(tenant)
    out = [sched.result(tenant, t) for t in tickets]
    return (np.asarray([m for m, _ in out]), np.asarray([v for _, v in out]))


def routed_path(stats, before: tuple[int, int]):
    """Path text of the routed flushes since ``before`` = (G=0 count,
    routed count): how many ran the G = 0 program, the rest overflow."""
    def text():
        g0 = stats.n_g0_batches - before[0]
        n = stats.n_routed_batches - before[1]
        return (f"routed two-bucket, G=0 on {g0}/{n} flushes, overflow on "
                f"{n - g0}")
    return text


# ---------------------------------------------------------------------------
# One chip.
# ---------------------------------------------------------------------------

def run_one_chip(sm: Smoke, z: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import api, pitc, support
    from repro.core import covariance as cov
    from repro.parallel.runner import VmapRunner
    from repro.serving import TenantScheduler

    M = z.machines
    params = gp_params()
    kfn = cov.make_spec("se", impl=IMPL)
    plain = cov.make_kernel("se")
    runner = VmapRunner(M=M)
    fit_path = f"rbf_pallas kernel (impl={IMPL}), VmapRunner M={M}"

    X, y, Xw, yw, U = sm.phase(
        f"data |D|={z.n} d={D} co-clustered M={M}, +{z.wave} wave rows, "
        f"{z.n_queries} queries",
        lambda: make_data(seed, z.n, M, z.wave, z.n_queries))
    S = sm.phase(f"support |S|={z.support} (greedy)",
                 lambda: support.select_support(plain, params, X, z.support))
    Xb, yb = X.reshape(M, -1, D), y.reshape(M, -1)
    ref = sm.phase("reference summaries (plain jnp f32)",
                   lambda: reference_summaries(params, S, [(Xb, yb)]))

    ppitc = sm.phase(f"fit ppitc |D|={z.n} M={M} |S|={z.support}",
                     lambda: api.fit("ppitc", kfn, params, X, y, S=S,
                                     runner=runner), path=fit_path)
    ppic = sm.phase(f"fit ppic |D|={z.n} M={M} |S|={z.support}",
                    lambda: api.fit("ppic", kfn, params, X, y, S=S,
                                    runner=runner), path=fit_path)

    # -- ppitc diag through the scheduler ------------------------------------
    sched = TenantScheduler()
    diag_path = ("fused xcov_diag" if kfn.fuse(z.support) else
                 "compose (|S| above the fused kernel's VMEM cap)")
    sched.admit("ppitc", ppitc, api.ServeSpec(max_batch=z.max_batch))
    check = mean_var_check(*reference_pitc(params, S, ref, U))
    for label in ("cold", "warm"):
        sm.phase(f"serve ppitc diag {label}, {z.n_queries} queries "
                 f"(flush {z.max_batch})",
                 lambda: serve_through_scheduler(sched, "ppitc", U),
                 path=diag_path, check=check)

    # -- assimilate one wave and hot-swap it: zero retraces ------------------
    store = sm.phase("init_store ppitc (the fitted data)",
                     lambda: api.init_store("ppitc", kfn, params, X, y, S=S,
                                            runner=runner), path=fit_path)
    same = all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(store.to_state()),
                   jax.tree.leaves(ppitc.state)))
    sm.note("init_store state vs api.fit state", same, "bitwise equal")
    tenant = sched.registry.get("ppitc")
    traces = tenant.plan.stats.n_traces
    wave_path = f"rank-{z.wave} Cholesky update of the whitened factor A_L"
    sm.phase(f"assimilate {z.wave}-row wave + hot-swap (commit_store)",
             lambda: (sched.commit_store("ppitc", store.assimilate(Xw, yw)),
                      tenant.model.state)[1], path=wave_path)
    sm.phase(f"assimilate the {z.wave}-row wave again, warm (not committed)",
             lambda: store.assimilate(Xw, yw).store.A_L, path=wave_path)
    ref_w = reference_summaries(
        params, S, [(Xb, yb), (Xw.reshape(M, -1, D), yw.reshape(M, -1))])
    sm.phase("serve ppitc diag after hot-swap",
             lambda: serve_through_scheduler(sched, "ppitc", U),
             path=diag_path,
             check=mean_var_check(*reference_pitc(params, S, ref_w, U)))
    retraces = tenant.plan.stats.n_traces - traces
    sm.note("hot-swap retraces", retraces == 0, f"{retraces} (expected 0)")

    # -- routed ppic through the scheduler -----------------------------------
    sched.admit("ppic", ppic, api.ServeSpec(routed=True,
                                            max_batch=z.max_batch))
    check = mean_var_check(*reference_pic(
        params, S, Xb, ref, U, jnp.asarray(nearest_block(U, Xb))))
    pstats = sched.registry.get("ppic").plan.stats
    for label in ("cold", "warm"):
        before = (pstats.n_g0_batches, pstats.n_routed_batches)
        sm.phase(f"serve ppic routed {label}, {z.n_queries} queries "
                 f"(flush {z.max_batch})",
                 lambda: serve_through_scheduler(sched, "ppic", U),
                 path=routed_path(pstats, before), check=check)

    # -- one fused xcov_diag batch at |S| = 1024, block_q = 64 ---------------
    s1 = z.fused_support
    S1 = S[:s1]    # greedy order: the prefix is the greedy |S| = 1024 set
    kfn_q = cov.make_spec("se", impl=IMPL, block_q=z.fused_block_q)
    fused = sm.phase(f"fit ppitc |D|={z.n} M={M} |S|={s1}",
                     lambda: api.fit("ppitc", kfn_q, params, X, y, S=S1,
                                     runner=runner), path=fit_path)
    sched.admit("fused", fused, api.ServeSpec(block_q=z.fused_block_q,
                                              max_batch=z.fused_batch))
    plan = sched.registry.get("fused").plan
    Uf = np.asarray(U)[:z.fused_batch - 1]
    hlo = plan._diag_exec().lower(
        plan.params, plan.state, plan.caches,
        np.zeros((plan.bucket_for(Uf.shape[0]), D), np.float32)).as_text()
    sm.note("fused serve program holds the Pallas kernel",
            "tpu_custom_call" in hlo,
            f"kfn.fuse({s1}) = {kfn_q.fuse(s1)}; tpu_custom_call in the "
            f"lowered program: {'tpu_custom_call' in hlo}")
    ref1 = reference_summaries(params, S1, [(Xb, yb)])
    sm.phase(f"serve fused batch of {Uf.shape[0]} (scheduler predict)",
             lambda: sched.predict("fused", Uf),
             path=(f"fused xcov_diag, block_q {plan.block_q}, bucket "
                   f"{plan.bucket_for(Uf.shape[0])}"),
             check=mean_var_check(*reference_pitc(params, S1, ref1, Uf)))

    # -- routed ppic against the dense PIC oracle ----------------------------
    n_o = z.oracle_n
    Xo, yo, _, _, Uo = make_data(seed + 1, n_o, M, 0, z.n_queries)
    So = support.select_support(plain, params, Xo, z.oracle_support)
    small = sm.phase(f"fit ppic |D|={n_o} M={M} |S|={z.oracle_support}",
                     lambda: api.fit("ppic", kfn, params, Xo, yo, S=So,
                                     runner=runner), path=fit_path)
    sched.admit("oracle", small, api.ServeSpec(routed=True,
                                               max_batch=z.max_batch))
    ostats = sched.registry.get("oracle").plan.stats
    served = sm.phase(f"serve ppic routed |D|={n_o}, {z.n_queries} queries",
                      lambda: serve_through_scheduler(sched, "oracle", Uo),
                      path=routed_path(ostats, (0, 0)))
    assign = jnp.asarray(nearest_block(Uo, Xo.reshape(M, -1, D)))
    lit = sm.phase(
        f"dense PIC oracle |D|={n_o} (eqs. 15-18, routed)",
        lambda: _ref_precision(pitc.pic_predict_literal_routed)(
            plain, params, So, Xo, yo, Uo, M, assign),
        check=lambda o: both(
            close("served mean", served[0], o.mean, ORACLE_TOL_MEAN),
            close("served var", served[1], jnp.diag(o.cov),
                  ORACLE_TOL_VAR)))
    del lit


# ---------------------------------------------------------------------------
# Four chips: the M machines as devices.
# ---------------------------------------------------------------------------

BLOCK_FIELDS = ("Xb", "yb", "V", "C_L", "Wy", "centroids")


def one_block_per_device(a, M: int) -> bool:
    shards = a.addressable_shards
    return (len(shards) == M and len({s.device for s in shards}) == M
            and all(s.data.shape[0] == 1 for s in shards))


def state_leaf_check(field: str, a, b):
    """One leaf of two fitted states compared. The weights w = A_L⁻¹L⁻¹ÿ
    carry A's condition, which grows with |D|: float32 round-off in A
    alone (A_L within ~3e-7) moved w by 8.4e-5 at |D| = 8,000 and 4.4e-4
    at |D| = 16,000 between the runners (CPU, |S| = 1,024 and 2,048). So
    w is checked through one shared factor, the second state's A_L:
    A_L·w = L⁻¹ÿ must agree; the direct difference is printed beside it."""
    import numpy as np
    if field != "w":
        return close(field, getattr(a, field), getattr(b, field), TOL)
    A_L = np.asarray(b.A_L, np.float64)
    ok, text = close("w (as A_L w, one A_L)",
                     *(A_L @ np.asarray(s.w, np.float64) for s in (a, b)),
                     W_TOL)
    return ok, f"{text}; w direct: {close('w', a.w, b.w, TOL)[1]}"


def run_four_chips(sm: Smoke, z: Sizes, seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import api, support
    from repro.core import covariance as cov
    from repro.launch.mesh import make_mesh
    from repro.parallel.runner import ShardMapRunner, VmapRunner
    from repro.serving import TenantScheduler

    M = z.four_machines
    params = gp_params()
    kfn = cov.make_spec("se", impl=IMPL)
    plain = cov.make_kernel("se")
    mesh = make_mesh((M,), ("data",), devices=jax.devices()[:M])
    runners = {"shard_map": ShardMapRunner(mesh=mesh, axis_name="data"),
               "vmap": VmapRunner(M=M)}

    X, y, _, _, U = sm.phase(
        f"data |D|={z.n} d={D} co-clustered M={M}, {z.n_queries} queries",
        lambda: make_data(seed, z.n, M, 0, z.n_queries))
    S = sm.phase(f"support |S|={z.support} (greedy)",
                 lambda: support.select_support(plain, params, X, z.support))

    sched = TenantScheduler()
    for method in ("ppitc", "ppic"):
        fits = {}
        for rname, runner in runners.items():
            fits[rname] = sm.phase(
                f"fit {method} {rname} |D|={z.n} M={M} |S|={z.support}",
                lambda: api.fit(method, kfn, params, X, y, S=S,
                                runner=runner),
                path=(f"{M} blocks over {len(mesh.devices.flat)} devices"
                      if rname == "shard_map" else f"{M} blocks on device 0"))
        a, b = fits["shard_map"].state, fits["vmap"].state
        sm.note(f"{method} state shard_map vs vmap", *both(*(
            state_leaf_check(f, a, b) for f in a._fields)))
        if method == "ppic":
            for f in BLOCK_FIELDS:
                arr = getattr(a, f)
                sm.note(f"placement {method} {f} {tuple(arr.shape)}",
                        one_block_per_device(arr, M),
                        "one block per device")
        # the default serving spec: the plan of the sharded state serves it
        # where it sits, by one program over the four devices, and says
        # which cross-covariance kernel it took (``kernel_note``)
        spec = api.ServeSpec(routed=(method == "ppic"), max_batch=z.max_batch)
        for rname, model in fits.items():
            sched.admit(f"{method}/{rname}", model, spec)
        outs = {}
        for label in ("cold", "warm"):
            for rname in fits:
                tenant = sched.registry.get(f"{method}/{rname}")
                stats = tenant.plan.stats
                route = (routed_path(stats, (stats.n_g0_batches,
                                             stats.n_routed_batches))
                         if method == "ppic" else lambda: "compose")
                n_dev = api.state_device_count(tenant.model.state)
                kernel = (tenant.plan.kernel_note or
                          f"{tenant.plan.kfn.resolved_impl()} "
                          f"cross-covariances")
                outs[rname] = sm.phase(
                    f"serve {method} {rname} {label}, batch of {z.n_queries}"
                    f" (scheduler predict, default ServeSpec)",
                    lambda: sched.predict(f"{method}/{rname}",
                                          np.asarray(U)),
                    path=lambda: (f"{route()}; {kernel}; state on {n_dev} "
                                  f"device(s)"))
        sm.note(f"{method} served shard_map vs vmap",
                *mean_var_check(*outs["vmap"])(outs["shard_map"]))


# ---------------------------------------------------------------------------
# Entry point.
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip path; 4: only the sharded fit "
                         "across four chips and its vmap comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable from {SRC} "
              f"({e})", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
              f"device(s))", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"[info] device {dev.device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)

    sm = Smoke()
    z = paper_sizes()
    if args.chips == 4:
        run_four_chips(sm, z, args.seed)
    else:
        run_one_chip(sm, z, args.seed)
    print(f"[info] compile cache total: {sm.cache.hits} hits, "
          f"{sm.cache.misses} misses", flush=True)
    if sm.failed:
        print(f"chip_smoke: {len(sm.failed)} check(s) failed: {sm.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
