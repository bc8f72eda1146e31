"""Unified GP method API: ``fit -> PosteriorState -> plan -> serve``.

The paper's real-time claim rests on amortization: everything that is
O((|D|/M)^3) or O(|S|^3) happens ONCE at fit time and is cached in a
per-method ``PosteriorState`` (a pure-array NamedTuple, hence a pytree that
jits, shards, checkpoints, and hot-swaps); a repeated query then costs only
the cross-covariances against the cached factors — O(|U||S| + |S|^2) for the
summary methods instead of re-running the local Cholesky pipeline.

Serving is TWO-phase (the plan/execute split):

* phase 1 — ``GPMethod.plan(kfn, params, state, spec) -> ServePlan``: a
  ``ServeSpec`` declares every per-deployment serving decision ONCE (kernel
  spec, query tile, bucket ladder, routed dispatch, overflow-executable
  ladder, backend caches, dtype policy), and the plan owns what was
  precompiled for that state: jitted executables per bucket (and, for
  routed pPIC, per overflow-group count) plus backend caches such as the
  per-block ``C⁻¹`` that turns the per-flush batched triangular solve into
  a batched matmul. ``plan.rebind(state)`` hot-swaps the posterior while
  REUSING every executable (zero recompilation when the state keeps its
  treedef/shapes) — the serving fleet's assimilate/retire path.
* phase 2 — ``plan.diag(U)`` / ``plan.routed_diag(U)`` / ``plan.full(U)``:
  the only predict entry points serving uses. ``FittedGP.predict*`` and
  ``launch.gp_serve.GPServer`` are thin clients of a plan (the legacy
  per-call ``GPMethod.predict*`` shim surface is gone — one deprecation
  cycle, as promised). ``ServeSpec.compat_key`` names the resolved policy
  so the multi-tenant registry (``serving/``) can share one executable
  lineage across plan-compatible deployments.

Three structural layers below the plans:

* per-method states   — ``FGPState`` / ``PITCState`` / ``PICState`` /
  ``PICFState``, defined here so core modules, runners, serving, and
  checkpointing all agree on the cached representation;
* ``GPMethod``        — (name, fit, predict impls, plan builder) registered
  by each core module at import; ``get``/``names`` look methods up by
  string, which is what examples/benchmarks/serving use;
* ``FittedGP``        — convenience pairing of (method, kfn, params, state)
  with plan-backed ``predict``/``predict_diag`` and ``with_state`` (which
  rebinds any already-built plans).

Fit is runner-agnostic: the summary/factor construction goes through
``parallel.runner.Runner.map``, so ``VmapRunner`` and ``ShardMapRunner``
produce the same state pytree (tested in tests/test_shardmap.py).

On top of the cached states sits the incremental-state layer (Sec. 5.2):
``StateStore`` is the method-owned protocol that unifies cold fits,
streaming assimilation, machine retirement, and checkpointing — a cold fit
is just ``init_store(...).to_state()``, and every later mutation reuses the
already-paid O(b³)/O(|S|³) work (``core/online.py`` for pPITC/pPIC,
``core/picf.py`` for the ICF factor). ``core/serialize.py`` persists every
registered state — and the stores themselves — with versioned schemas so
serving fleets can checkpoint, restore, replicate, and keep assimilating.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Protocol, runtime_checkable

import jax
import numpy as np

from repro.core import covariance, linalg
from repro.parallel.runner import ROUTED_ALPHA
from repro.runtime import spans


# ---------------------------------------------------------------------------
# Per-method posterior states (pure-array pytrees).
# ---------------------------------------------------------------------------

class FGPState(NamedTuple):
    """Exact GP: cached |D|x|D| Cholesky + weights (eqs. 1-2)."""
    X: jax.Array        # (n, d) training inputs
    L: jax.Array        # (n, n) chol(K_DD + noise)
    alpha: jax.Array    # (n,)   (K_DD + noise)^{-1} y


class PITCState(NamedTuple):
    """PITC/pPITC: everything global lives in S-space (eqs. 5-8), whitened
    by L = chol K_SS: Sigma-dot-dot = L A_L A_Lᵀ Lᵀ (core/ppitc.py)."""
    S: jax.Array        # (s, d) support set
    Kss_L: jax.Array    # (s, s) chol K_SS
    A_L: jax.Array      # (s, s) chol L^{-1} Sdd L^{-T}  (eq. 6, whitened)
    w: jax.Array        # (s,)   A_L^{-1} L^{-1} ydd     (eq. 7 weights)


class PICState(NamedTuple):
    """PIC/pPIC: PITC globals + per-block caches for the local correction
    (eqs. 12-14). Leading axis of the block fields is the machine axis M.

    ``centroids`` realizes Remark 2 on the serving side: the per-block data
    centroids fixed at fit time let ``ppic.predict_routed`` assign each query
    to the block whose local data best explains it, independent of how the
    query batch happens to be composed."""
    S: jax.Array        # (s, d)
    Kss_L: jax.Array    # (s, s)
    A_L: jax.Array      # (s, s)  as PITCState
    w: jax.Array        # (s,)    as PITCState
    Xb: jax.Array       # (M, b, d) data blocks
    yb: jax.Array       # (M, b)
    V: jax.Array        # (M, s, b) Kss_L^{-1} K_S,Dm (whitened, see ppic)
    C_L: jax.Array      # (M, b, b) chol Sigma_{DmDm|S}
    Wy: jax.Array       # (M, b)    C^{-1} y_m
    centroids: jax.Array  # (M, d)  block centroids (query routing targets)


class PICFState(NamedTuple):
    """pICF-based GP: distributed ICF factor + cached R-space solves
    (eqs. 19-23)."""
    Xb: jax.Array       # (M, b, d)
    yb: jax.Array       # (M, b)
    F: jax.Array        # (M, R, b) per-machine factor columns
    Phi_L: jax.Array    # (R, R)   chol(I + sum_m F_m F_m^T / s2)
    ydd: jax.Array      # (R,)     Phi^{-1} sum_m F_m y_m  (eq. 22)


# ---------------------------------------------------------------------------
# Incremental-state protocol (Sec. 5.2 summary algebra, method-owned).
# ---------------------------------------------------------------------------

@runtime_checkable
class StateStore(Protocol):
    """What a method's incremental state container must support.

    A store owns everything ``fit`` needed (kernel, hyperparameters, support
    set / rank, runner) plus the cached per-machine contributions, so the
    update algebra is closed over it:

    * ``assimilate(X_new, y_new)`` — fold a new data stream in as fresh
      machine blocks, reusing every already-paid local factorization (the
      paper's streaming add);
    * ``retire(machine)`` / ``revive(machine)`` — subtract / re-add one
      machine's contribution (failure, decommission, straggler deadline);
    * ``to_state()`` — assemble the method's cached ``PosteriorState`` from
      whatever machines are alive. Incremental by contract: implementations
      keep the expensive global factor maintained via rank-b Cholesky
      updates (``linalg.chol_update_rank``), so this is O(|S|²) per call,
      not O(|S|³).

    Stores are immutable: every mutation returns a new store, so serving can
    hold the old one until the hot-swap commits. All methods are host-side
    (they orchestrate jitted device work but are not themselves jitted).
    """

    def assimilate(self, X_new, y_new) -> "StateStore": ...

    def retire(self, machine: int) -> "StateStore": ...

    def revive(self, machine: int) -> "StateStore": ...

    def to_state(self) -> Any: ...


def state_device_count(state) -> int:
    """How many devices the arrays of ``state`` span (1 for host arrays and
    traced values)."""
    devices = set()
    for a in jax.tree.leaves(state):
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            devices |= a.devices()
    return max(len(devices), 1)


def check_machine_index(n_machines: int, machine: int) -> None:
    """Shared retire/revive guard: reject out-of-range machine ids up
    front. jnp clamps OOB gathers but silently DROPS OOB scatter updates,
    so an unchecked bad index would downdate a clamped machine's cached
    factor while leaving the alive mask untouched — silent store corruption
    instead of an error."""
    if not 0 <= machine < n_machines:
        raise IndexError(
            f"machine {machine} out of range for {n_machines} machines")


def concrete_alive_mask(alive) -> np.ndarray | None:
    """Host view of a store's alive mask, or ``None`` while tracing.

    Host-side maintenance ops (retire/revive no-op checks, ``to_state``
    compaction) need Python truthiness on the mask — which is exactly the
    ``TracerBoolConversionError`` bug class that hit ``PICStore.to_state``
    (lint rule JIT001). Every such branch goes through this guard and
    handles the ``None`` case explicitly: either a clear TypeError
    (data-dependent host work, impossible under trace) or the all-alive
    fast path (a traced store is all-alive by construction, because the
    single-machine mutators reject traced masks)."""
    if isinstance(alive, jax.core.Tracer):
        return None
    return np.asarray(alive)


# ---------------------------------------------------------------------------
# ServeSpec — phase 1's input: every per-deployment serving decision, once.
# ---------------------------------------------------------------------------

def default_buckets(max_batch: int, *, min_bucket: int = 8,
                    block_q: int = 1) -> tuple[int, ...]:
    """Powers of two from min_bucket up, capped by max_batch (inclusive),
    each rounded up to a multiple of ``block_q``.

    ``block_q`` is the Pallas serving kernel's query-tile size: emitting
    bucket sizes on tile boundaries means the jitted predict's padded batch
    IS the kernel grid — no second pad inside the kernel dispatch (the
    fused ``xcov_diag`` and the two-bucket routed scatter both consume the
    same alignment). The bare default 1 keeps direct calls' ladders ending
    exactly at max_batch; powers of two >= 8 are already 8-aligned, so the
    historical ladder is unchanged under the server default block_q=8.

    Ladder invariants (regression-tested exhaustively in
    tests/test_api_state.py and tests/test_plan.py):

    * covering — the top bucket is >= max_batch even when ``max_batch <
      min_bucket`` or ``max_batch`` is not tile-aligned (the top entry is
      ``max_batch`` rounded UP to the tile, never truncated down);
    * sorted and duplicate-free — a duplicate bucket would compile the same
      executable twice and skew padding stats, so the ladder is squeezed
      through ``dict.fromkeys`` regardless of how the loop, the rounding,
      and the trailing ``max_batch`` append interact;
    * validated — non-positive ``max_batch``/``min_bucket``/``block_q``
      raise instead of emitting a 0-bucket or looping forever
      (``min_bucket=0`` used to hang the doubling loop).
    """
    if max_batch < 1 or min_bucket < 1 or block_q < 1:
        raise ValueError(
            f"default_buckets needs positive sizes; got max_batch="
            f"{max_batch}, min_bucket={min_bucket}, block_q={block_q}")
    align = lambda v: -(-v // block_q) * block_q
    sizes = []
    b = min_bucket
    while b < max_batch:
        sizes.append(align(b))
        b *= 2
    sizes.append(align(max_batch))
    return tuple(dict.fromkeys(sizes))


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """Frozen per-deployment serving policy — phase 1's single input.

    Everything ``predict_diag``/``predict_routed_diag`` used to re-decide
    per call (ad-hoc ``tile=`` kwargs, ``KernelSpec`` threading, server-side
    bucket ladders) is declared here once; ``GPMethod.plan`` turns it into a
    ``ServePlan`` whose executables and caches realize the policy.

    * ``kernel``   — a ``cov.KernelSpec`` overriding the fit-time kernel
      callable (how cross-covariances are built: dense jnp vs Pallas vs the
      fused ``xcov_diag``); ``None`` serves with the kernel the plan was
      built with.
    * ``block_q``  — serving query-tile size. Resolution order: this field,
      then the kernel's declared ``block_q``, then the f32 sublane (8).
      Bucket ladders AND the routed scatter's capacity both land on this
      boundary.
    * ``max_batch`` / ``buckets`` / ``min_bucket`` — the bucket ladder.
      Explicit ``buckets`` win; otherwise ``default_buckets(max_batch,
      min_bucket, block_q)``; with NEITHER declared the plan serves every
      batch at its exact size (identity bucketing — the legacy direct-call
      behavior, bitwise; the PIC family's positional path assigns queries
      to blocks by batch position, so padding is a posterior-visible
      decision the spec must own, not a silent default). Oversized batches
      round up to a multiple of the top bucket (never under-covered).
    * ``routed``   — serve through the batch-composition-invariant
      centroid-routed path (PIC family only); ``GPServer`` consumes this.
    * ``alpha``    — routed main-bucket capacity multiplier (headroom vs
      skew, see ``runner.scatter_two_bucket``).
    * ``max_overflow_groups`` — bounds the routed overflow-executable
      ladder: flush-time group counts snap up within {0, 1, 2, 4, ...};
      a demand above this cap runs the full worst-case-G program instead of
      compiling a dedicated one. ``None`` = the full power-of-two ladder.
    * ``cached_cinv`` — precompute per-block ``C⁻¹ = (C_L C_Lᵀ)⁻¹`` at plan
      build so the per-flush batched triangular solve becomes ONE batched
      matmul (pays where batched trsm bills per program — XLA-CPU, small-RHS
      TPU). Off by default: the matmul takes a different float path, and the
      default plan is bitwise-faithful to the legacy trsm serving path.
    * ``dtype``    — query dtype policy: ``"preserve"`` (serve in whatever
      dtype queries arrive, the legacy behavior), ``"state"`` (cast queries
      to the state's dtype so one executable serves mixed-precision
      callers), ``"float32"``.

    Frozen/hashable: a spec is a cache key (``FittedGP`` memoizes one plan
    per spec) and safe to close over in jitted code.
    """
    kernel: Any = None
    block_q: int | None = None
    max_batch: int | None = None
    buckets: tuple[int, ...] | None = None
    min_bucket: int = 8
    routed: bool = False
    alpha: int = ROUTED_ALPHA
    max_overflow_groups: int | None = None
    cached_cinv: bool = False
    dtype: str = "preserve"

    def __post_init__(self):
        # fail at construction, not deep inside routed_capacity at flush
        # time (alpha=0 would divide by zero there; alpha<0 a garbage
        # layout; the pad-packing invariant M*cap >= bucket needs alpha>=1)
        if self.alpha < 1:
            raise ValueError(f"ServeSpec.alpha must be >= 1; got "
                             f"{self.alpha}")
        if self.max_overflow_groups is not None \
                and self.max_overflow_groups < 0:
            raise ValueError(f"ServeSpec.max_overflow_groups must be >= 0; "
                             f"got {self.max_overflow_groups}")
        if self.cached_cinv and not self.routed:
            # the C^-1 cache is consumed by the routed flush executables
            # only; building it for a diag-only plan would pay O(M b^3)
            # per rebind for zero effect
            raise ValueError(
                "ServeSpec(cached_cinv=True) serves the routed flush path; "
                "set routed=True as well")

    def resolve_kfn(self, kfn: Callable) -> Callable:
        served = self.kernel if self.kernel is not None else kfn
        if self.block_q is not None:
            from repro.core import covariance as cov
            if isinstance(served, cov.KernelSpec) and \
                    served.block_q != self.block_q:
                # the spec's tile overrides the kernel's: the fused
                # xcov_diag dispatch reads the KernelSpec's block_q, and a
                # mismatch would re-pick a tile and pad the bucket AGAIN
                # inside the dispatch — the second pad the bucket-ladder
                # alignment exists to avoid
                served = dataclasses.replace(served, block_q=self.block_q)
        return served

    def resolve_block_q(self, kfn: Callable) -> int:
        if self.block_q is not None and self.block_q < 1:
            raise ValueError(f"ServeSpec.block_q must be a positive tile "
                             f"size; got {self.block_q}")
        kfn = self.resolve_kfn(kfn)
        return self.block_q or getattr(kfn, "block_q", None) or 8

    def resolve_buckets(self, kfn: Callable) -> tuple[int, ...] | None:
        """The ladder, or ``None`` for identity bucketing (no padding)."""
        if self.buckets is not None:
            buckets = tuple(sorted(dict.fromkeys(self.buckets)))
            if not buckets or buckets[0] < 1:
                raise ValueError(f"ServeSpec.buckets must be positive; got "
                                 f"{self.buckets}")
            if self.max_batch is not None and buckets[-1] < self.max_batch:
                raise ValueError(
                    f"largest bucket {buckets[-1]} < max_batch "
                    f"{self.max_batch}: the ladder would under-cover the "
                    f"serving queue")
            return buckets
        if self.max_batch is None:
            return None
        return default_buckets(self.max_batch, min_bucket=self.min_bucket,
                               block_q=self.resolve_block_q(kfn))

    def compat_key(self, kfn: Callable) -> tuple:
        """Hashable identity of the COMPILED serving policy this spec
        resolves to over fit-time kernel ``kfn``.

        Two deployments whose compat keys match run byte-identical serving
        programs: same resolved kernel callable, tile, bucket ladder, routed
        dispatch, overflow ladder bound, backend caches, and dtype policy.
        Everything that is a TRACED argument of the executables — params,
        state, caches — is deliberately absent: executables are compiled
        per argument SHAPE, so deployments differing only in posterior
        values can share one executable lineage (the multi-tenant registry
        combines this key with the method name and the state/params tree
        structure to decide lineage sharing; ``serving/registry.py``).

        Distinct specs can map to one key (e.g. ``block_q=None`` vs an
        explicit ``block_q`` equal to the kernel's declared tile): the key
        captures the RESOLVED policy, which is what the compiled programs
        depend on.
        """
        served = self.resolve_kfn(kfn)
        try:
            hash(served)
        except TypeError:       # bespoke closure: identity is the best key
            served = id(served)
        return (served, self.resolve_block_q(kfn), self.resolve_buckets(kfn),
                self.routed, self.alpha, self.max_overflow_groups,
                self.cached_cinv, self.dtype)


# ---------------------------------------------------------------------------
# ServePlan — phase 1's output: executables + caches, owned per state.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PlanStats:
    """Shared across ``rebind`` generations — the executable cache and its
    counters describe the plan LINEAGE, which is what the zero-recompile
    guarantee is about (tests probe ``n_traces`` across hot-swaps)."""
    n_traces: int = 0          # jit traces across all executables
    n_diag_batches: int = 0
    n_routed_batches: int = 0
    n_full_batches: int = 0
    n_padded_rows: int = 0
    n_g0_batches: int = 0      # routed flushes served by the G=0 program
    last_g: int | None = None  # overflow-group count of the last routed call
    # bounded degradation (PIC family): rows answered from the global
    # S-space posterior because their routed block was marked dead
    n_degraded_rows: int = 0
    last_degraded: Any = None  # (u,) bool of the last routed call, or None


@dataclasses.dataclass(frozen=True)
class ServePlan:
    """Executable serving program for ONE (method, kernel, spec, state).

    Owns (a) the resolved serving policy (kernel callable, tile, bucket
    ladder), (b) jitted executables, created once per entry point and
    reused across every ``rebind`` (the executable cache dict is shared by
    reference), and (c) ``caches`` — method-specific precomputed backend
    state (``None`` here; pPIC's plan carries per-block ``C⁻¹``) that is
    passed to executables as a TRACED argument, so refreshing it on rebind
    never recompiles.

    Entry points (phase 2):

    * ``diag(U)``        — (mean, var) for any |U|; host-side pad to the
      bucket ladder, one jitted dispatch, trim;
    * ``routed_diag(U)`` — the batch-composition-invariant path (PIC
      family; raises here);
    * ``full(U)``        — the method's native posterior (dense/block
      covariance view), un-padded (the covariance shape is the point);
    * ``rebind(state)``  — same plan, new posterior: every executable is
      reused, so a same-shape hot-swap costs zero recompilation and a
      grown block axis costs exactly one re-trace per entry point.

    Padding/staging is host-side NumPy throughout (device-stage a
    microbatch and every distinct queue length eagerly compiles a fresh
    stack/pad kernel — the tail-latency lesson baked into GPServer).
    """
    method: "GPMethod"
    kfn: Callable
    params: dict
    state: Any
    spec: ServeSpec
    block_q: int
    buckets: tuple[int, ...]
    caches: Any = None
    stats: PlanStats = dataclasses.field(default_factory=PlanStats)
    _exec: dict = dataclasses.field(default_factory=dict)
    # why the plan serves with another kernel than the one it was asked
    # for (``GPMethod.plan``); None when it serves the requested kernel
    kernel_note: str | None = None

    # -- ladder -------------------------------------------------------------

    def bucket_for(self, u: int) -> int:
        if self.buckets is None:        # identity bucketing: exact batches
            return u
        for b in self.buckets:
            if b >= u:
                return b
        big = self.buckets[-1]          # oversized: multiple of the top
        return -(-u // big) * big

    def _staged(self, U):
        """Apply the spec's dtype policy. Zero-copy under ``"preserve"``
        (device arrays stay on device; ``plan.diag``/``plan.full`` remain
        jax-traceable when no bucket padding fires), device-/trace-side
        cast for jax values otherwise."""
        if self.spec.dtype == "preserve":
            return U
        if self.spec.dtype == "state":
            target = jax.tree.leaves(self.state)[0].dtype
        elif self.spec.dtype == "float32":
            target = np.float32
        else:
            raise ValueError(
                f"unknown ServeSpec.dtype policy {self.spec.dtype!r}; "
                f"expected 'preserve', 'state', or 'float32'")
        if isinstance(U, (np.ndarray, list, tuple)):
            return np.asarray(U, dtype=target)
        return U.astype(target)          # jax array / tracer: no host trip

    def _padded(self, U) -> tuple[Any, int]:
        U = self._staged(U)
        u = U.shape[0]
        bucket = self.bucket_for(u)
        if bucket == u:
            return U, u
        if isinstance(U, jax.core.Tracer):
            # inside an outer jit the pad must stay on device; u and the
            # bucket are static under trace, and compile-per-batch-length
            # is the OUTER program's choice (host serving traffic never
            # takes this branch)
            pad = jax.numpy.zeros((bucket - u,) + tuple(U.shape[1:]),
                                  U.dtype)
            self.stats.n_padded_rows += bucket - u   # counted per trace
            return jax.numpy.concatenate([U, pad]), u
        # padding is host-side serving staging by design (an eager device
        # pad would compile once per distinct batch length — the serving
        # tail-latency failure mode); bucket ladders are a serving policy
        Un = np.asarray(U)
        buf = np.zeros((bucket,) + Un.shape[1:], Un.dtype)
        buf[:u] = Un
        self.stats.n_padded_rows += bucket - u
        return buf, u

    # -- executables ----------------------------------------------------------

    def _jitted(self, key: str, build: Callable[[], Callable]) -> Callable:
        """One jitted executable per key, created lazily, shared across
        rebinds. ``build`` returns the python callable to jit; a trace
        counter rides inside it so the lifecycle tests can assert the
        zero-recompile hot-swap contract. The program is named after its
        key (``jit_serve_diag``, ``jit_serve_routed_g2``), so the entry
        points stay apart in a profiler trace."""
        fn = self._exec.get(key)
        if fn is None:
            inner = linalg.f32_matmuls(build())
            stats = self.stats

            def counted(*args):
                stats.n_traces += 1
                return inner(*args)

            counted.__name__ = counted.__qualname__ = "serve_" + (
                key if isinstance(key, str) else f"{key[0]}_g{key[1]}")
            fn = self._exec[key] = jax.jit(counted)
        return fn

    def _diag_exec(self) -> Callable:
        impl, kfn = self.method.predict_diag_fn, self.kfn
        return self._jitted(
            "diag", lambda: lambda params, state, caches, U:
                impl(kfn, params, state, U))

    def _full_exec(self) -> Callable:
        impl, kfn = self.method.predict_fn, self.kfn
        return self._jitted(
            "full", lambda: lambda params, state, caches, U:
                impl(kfn, params, state, U))

    # -- phase 2 entry points -------------------------------------------------

    def diag(self, U) -> tuple[jax.Array, jax.Array]:
        """(mean, var) over a (u, d) batch — THE serving hot path."""
        with spans.span("plan.pad"):
            Up, u = self._padded(U)
        with spans.span("plan.exec"):
            mean, var = self._diag_exec()(self.params, self.state,
                                          self.caches, Up)
        self.stats.n_diag_batches += 1
        with spans.span("plan.trim"):
            return mean[:u], var[:u]

    def routed_diag(self, U, block_alive=None):
        """Generic routed path: the method's raw routed impl, jitted with
        the spec's tile. Methods with a specialized plan (pPIC/PIC's
        ``PICServePlan``) override this with backend caches, the
        overflow-executable ladder, and bounded degradation
        (``block_alive``); methods with no routed impl raise —
        their posterior is composition-invariant already, use ``diag``."""
        impl, kfn, tile = (self.method.predict_routed_diag_fn, self.kfn,
                           self.block_q)
        if block_alive is not None:
            raise ValueError(
                f"method {self.method.name!r}'s generic routed plan has no "
                f"bounded-degradation path (block_alive); only the PIC "
                f"family's PICServePlan serves dead-block traffic from the "
                f"global posterior")
        self.stats.last_degraded = None
        if impl is None:
            raise ValueError(
                f"method {self.method.name!r} has no routed serving "
                f"program; its posterior does not depend on query-block "
                f"assignment — use plan.diag")
        with spans.span("plan.pad"):
            Up, u = self._padded(U)
        fn = self._jitted(
            "routed", lambda: lambda params, state, caches, U:
                impl(kfn, params, state, U, tile=tile))
        with spans.span("plan.exec"):
            mean, var = fn(self.params, self.state, self.caches, Up)
        self.stats.n_routed_batches += 1
        self.stats.last_g = None
        with spans.span("plan.trim"):
            return mean[:u], var[:u]

    def full(self, U):
        """The method's native posterior (mean + covariance view). Queries
        are NOT bucket-padded — the covariance block shape is the output."""
        post = self._full_exec()(self.params, self.state, self.caches,
                                 self._staged(U))
        self.stats.n_full_batches += 1
        return post

    # -- lifecycle ------------------------------------------------------------

    def rebind(self, state) -> "ServePlan":
        """Hot-swap the posterior: a new plan over ``state`` sharing this
        plan's executables and stats. Same treedef + leaf shapes -> every
        compiled program is reused (zero recompilation, probe-tested);
        changed shapes cost one re-trace per entry point on next use."""
        if covariance.xla_kernel(self.kfn) is not self.kfn \
                and state_device_count(state) > 1:
            raise ValueError(
                f"this plan serves with a Pallas kernel, which cannot run on "
                f"a state spread over {state_device_count(state)} devices; "
                f"build a new plan for it (method.plan / FittedGP.plan)")
        return dataclasses.replace(self, state=state,
                                   caches=self._rebuild_caches(state))

    def _rebuild_caches(self, state):
        """Recompute backend caches for a new state (no-op here)."""
        return None

    def warmup(self, d: int, *, dtype=np.float32) -> "ServePlan":
        """Compile every executable the serving loop can hit, up front
        (steady-state serving: one-time XLA compiles must not masquerade as
        tail latency): the diag program per bucket — or, for a routed spec,
        the routed program per bucket (specialized plans extend this to
        their whole overflow-executable ladder). ``d`` is the query feature
        dimension; a no-op under identity bucketing (no finite ladder)."""
        routed = (self.spec.routed
                  and self.method.predict_routed_diag_fn is not None)
        for b in self.buckets or ():
            U0 = np.zeros((b, d), dtype)
            jax.block_until_ready(
                (self.routed_diag(U0) if routed else self.diag(U0))[0])
        return self


# ---------------------------------------------------------------------------
# Method registry.
# ---------------------------------------------------------------------------

_DEFAULT_SPEC = ServeSpec()


@dataclasses.dataclass(frozen=True)
class GPMethod:
    """One GP regression method behind the uniform state API.

    ``fit(kfn, params, X, y, **kw) -> state`` where ``kw`` is the subset of
    (S=, M=, rank=, runner=) the method needs. The ``*_fn`` fields are the
    RAW prediction implementations (what plans jit):

    * ``predict_fn(kfn, params, state, U)``      -> native posterior;
    * ``predict_diag_fn(kfn, params, state, U)`` -> (mean, var) vectors;
    * ``predict_routed_diag_fn(..., tile=)``     -> the batch-composition-
      invariant path (PIC family; ``None`` for methods whose posterior is
      already independent of query-block assignment — fgp/pitc/ppitc/picf
      get the invariance for free and ``GPServer(routed=True)`` rejects
      them at construction);
    * ``plan_fn(method, kfn, params, state, spec)`` — method-owned
      ``ServePlan`` factory (``None`` -> the generic plan). pPIC/PIC
      install a plan carrying per-block ``C⁻¹`` caches and the
      per-overflow-group-count executable ladder.
    * ``init_store`` (optional) — the incremental-state entry point:
      ``init_store(kfn, params, X, y, **kw) -> StateStore`` with the same
      keyword subset as ``fit``. Methods without an incremental algebra
      (``fgp``) leave it ``None``; for the summary/factor methods ``fit``
      IS ``init_store(...).to_state()``.

    The legacy per-call ``method.predict*(kfn, params, state, U, **kw)``
    shim surface is GONE (it lived one deprecation cycle behind
    ``PlanDeprecationWarning``): every prediction goes through
    ``method.plan(...)`` / ``FittedGP`` / a serving runtime.
    """
    name: str
    fit: Callable[..., Any]
    predict_fn: Callable[..., Any]
    predict_diag_fn: Callable[..., Any]
    predict_routed_diag_fn: Callable[..., Any] | None = None
    init_store: Callable[..., "StateStore"] | None = None
    plan_fn: Callable[..., ServePlan] | None = None

    # -- phase 1 --------------------------------------------------------------

    def plan(self, kfn, params, state, spec: ServeSpec | None = None
             ) -> ServePlan:
        """Build the serving program for ``state`` under ``spec``."""
        spec = spec if spec is not None else _DEFAULT_SPEC
        if spec.cached_cinv and self.plan_fn is None:
            raise ValueError(
                f"ServeSpec(cached_cinv=True) but method {self.name!r} has "
                f"no backend-cache plan (only the PIC family serves from "
                f"per-block C factors)")
        # a state spread over several devices is served by one program
        # over all of them, which XLA partitions; it cannot partition a
        # Pallas (Mosaic) call, so such a plan builds its cross-covariances
        # in XLA and records why
        note = None
        n_dev = state_device_count(state)
        requested = spec.resolve_kfn(kfn)
        if n_dev > 1 and covariance.xla_kernel(requested) is not requested:
            spec = dataclasses.replace(
                spec, kernel=covariance.xla_kernel(requested))
            note = (f"XLA cross-covariances: the state spans {n_dev} "
                    f"devices and a Pallas kernel cannot be partitioned "
                    f"across them")
        if self.plan_fn is not None:
            plan = self.plan_fn(self, kfn, params, state, spec)
        else:
            plan = ServePlan(self, spec.resolve_kfn(kfn), params, state,
                             spec, spec.resolve_block_q(kfn),
                             spec.resolve_buckets(kfn))
        return plan if note is None else dataclasses.replace(
            plan, kernel_note=note)


REGISTRY: dict[str, GPMethod] = {}


def register(method: GPMethod) -> GPMethod:
    REGISTRY[method.name] = method
    return method


def get(name: str) -> GPMethod:
    if name not in REGISTRY:
        # methods self-register at module import; pull the core modules in
        from repro.core import gp, picf, pitc, ppic, ppitc  # noqa: F401
    try:
        return REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown GP method {name!r}; have {names()}")


def names() -> list[str]:
    return sorted(REGISTRY)


# ---------------------------------------------------------------------------
# FittedGP — what serving / examples hold on to.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FittedGP:
    """A fitted model: method + kernel + hyperparameters + cached state.

    A thin client of the two-phase API: every predict goes through a
    memoized ``ServePlan`` (one per ``ServeSpec``), so repeated calls reuse
    jitted executables and ``with_state`` (hot-swap after a ``StateStore``
    assimilate/retire) REBINDS the existing plans instead of rebuilding —
    zero recompilation when the state keeps its shapes.
    """
    method: GPMethod
    kfn: Callable
    params: dict
    state: Any

    def plan(self, spec: ServeSpec | None = None) -> ServePlan:
        """The serving program for this model under ``spec`` (memoized)."""
        spec = spec if spec is not None else _DEFAULT_SPEC
        plans = self.__dict__.setdefault("_plans", {})
        if spec not in plans:
            plans[spec] = self.method.plan(self.kfn, self.params, self.state,
                                           spec)
        return plans[spec]

    def predict(self, U: jax.Array):
        return self.plan().full(U)

    def predict_diag(self, U: jax.Array):
        return self.plan().diag(U)

    def predict_routed_diag(self, U: jax.Array):
        """Centroid-routed (mean, var) — batch-composition-invariant."""
        if self.method.predict_routed_diag_fn is None:
            raise ValueError(
                f"method {self.method.name!r} has no routed prediction path; "
                f"its posterior does not depend on query-block assignment — "
                f"use predict_diag")
        return self.plan().routed_diag(U)

    def with_state(self, state) -> "FittedGP":
        """Hot-swap the cached posterior (online assimilate/retire); any
        already-built plans are rebound, keeping their executables."""
        new = dataclasses.replace(self, state=state)
        plans = self.__dict__.get("_plans")
        if plans:
            new.__dict__["_plans"] = {sp: pl.rebind(state)
                                      for sp, pl in plans.items()}
        return new


def _method_kwargs(S=None, M=None, rank=None, runner=None) -> dict:
    kw = {}
    if S is not None:
        kw["S"] = S
    if M is not None:
        kw["M"] = M
    if rank is not None:
        kw["rank"] = rank
    if runner is not None:
        kw["runner"] = runner
    return kw


def fit(name: str, kfn, params, X, y, *, S=None, M=None, rank=None,
        runner=None) -> FittedGP:
    """Registry front door: fit method ``name`` and return a FittedGP."""
    method = get(name)
    state = method.fit(kfn, params, X, y,
                       **_method_kwargs(S, M, rank, runner))
    return FittedGP(method, kfn, params, state)


def init_store(name: str, kfn, params, X, y, *, S=None, M=None, rank=None,
               runner=None) -> StateStore:
    """Registry front door for the incremental-state protocol: build method
    ``name``'s ``StateStore`` from an initial data batch. The cold-fit state
    is ``store.to_state()``; later ``assimilate``/``retire`` calls mutate
    incrementally (see ``launch.gp_serve.GPServer.update``)."""
    method = get(name)
    if method.init_store is None:
        raise ValueError(
            f"method {name!r} has no incremental StateStore (its cached "
            f"state has no cheap update algebra); have "
            f"{[m for m in names() if REGISTRY[m].init_store is not None]}")
    return method.init_store(kfn, params, X, y,
                             **_method_kwargs(S, M, rank, runner))
