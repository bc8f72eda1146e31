"""Execution backends for per-machine GP programs.

The paper's algorithms are written ONCE as per-machine functions that use
``jax.lax`` collectives over ``axis_name`` (psum / all_gather / psum_scatter /
all_to_all — the TPU realization of the paper's MPI broadcast/reduce). A
Runner decides how the machine axis is realized:

* ``VmapRunner``    — `jax.vmap(axis_name=...)`: single-device simulation of M
  machines. Used by tests and CPU examples; bit-identical math.
* ``ShardMapRunner`` — `jax.shard_map` over one or more mesh axes: the real
  multi-device execution (multi-pod dry-run uses ("pod", "data")).

Both consume *stacked* inputs with a leading machine axis (M, ...) and return
stacked outputs (M, ...), so callers are backend-agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Runner:
    """Abstract machine-axis executor."""
    axis_name: Any = "machines"

    @property
    def num_machines(self) -> int:
        raise NotImplementedError

    def map(self, fn: Callable, sharded: Sequence, replicated: Sequence = ()):
        """Run per-machine ``fn(*block_args, *replicated_args)``.

        ``sharded`` entries are pytrees whose leaves carry a leading (M, ...)
        machine axis; ``fn`` sees them without it. Returns stacked outputs.
        ``fn``'s matmuls run at full f32 precision
        (``core.linalg.f32_matmuls``).
        """
        raise NotImplementedError

    def shard_blocks(self, X: jax.Array) -> jax.Array:
        """(n, ...) -> (M, n/M, ...) block layout (paper Def. 1).

        Training data must divide exactly — zero-padding data rows would
        corrupt the local summaries (a padded row adds a spurious noise-only
        observation to Sigma_{DmDm|S}). Query batches are row-independent and
        go through ``pad_blocks`` instead (the serving path).
        """
        M = self.num_machines
        n = X.shape[0]
        if n % M != 0:
            raise ValueError(
                f"n={n} does not divide among M={M} machines (Def. 1). "
                f"Either trim/re-block the data so M | n, or — for query "
                f"batches — use parallel.runner.pad_blocks(X, M), which "
                f"zero-pads and returns the valid count for trimming.")
        return X.reshape((M, n // M) + X.shape[1:])

    def pad_blocks(self, X: jax.Array) -> tuple[jax.Array, int]:
        """Zero-padded (M, ceil(n/M), ...) block layout; see ``pad_blocks``."""
        return pad_blocks(X, self.num_machines)

    def unshard(self, Xb: jax.Array) -> jax.Array:
        return Xb.reshape((-1,) + Xb.shape[2:])


@dataclasses.dataclass(frozen=True)
class VmapRunner(Runner):
    """Single-device simulation of M machines via vmap collectives."""
    M: int = 4

    @property
    def num_machines(self) -> int:
        return self.M

    def map(self, fn, sharded, replicated=()):
        from repro.core.linalg import f32_matmuls
        g = f32_matmuls(lambda *blocks: fn(*blocks, *replicated))
        return jax.vmap(g, axis_name=self.axis_name)(*sharded)


@dataclasses.dataclass(frozen=True)
class ShardMapRunner(Runner):
    """Real distribution over mesh axes.

    ``axis_name`` may be a single mesh axis ("data") or a tuple
    (("pod", "data")) — collectives inside per-machine code reduce over all of
    them; the number of machines is the product of the axis sizes.
    """
    mesh: Mesh | None = None

    def __post_init__(self):
        assert self.mesh is not None

    @property
    def axes(self) -> tuple[str, ...]:
        a = self.axis_name
        return (a,) if isinstance(a, str) else tuple(a)

    @property
    def num_machines(self) -> int:
        out = 1
        for a in self.axes:
            out *= self.mesh.shape[a]
        return out

    @property
    def spec(self) -> P:
        """Leading (machine) axis split over the machine mesh axes."""
        return P(self.axes if len(self.axes) > 1 else self.axes[0])

    def shard_blocks(self, X: jax.Array) -> jax.Array:
        """``Runner.shard_blocks`` placed one block per device, so data
        blocks sit where their machine's program runs."""
        return jax.device_put(super().shard_blocks(X),
                              NamedSharding(self.mesh, self.spec))

    def map(self, fn, sharded, replicated=()):
        from repro.core.linalg import f32_matmuls
        n_shard = len(sharded)
        spec = self.spec

        @f32_matmuls
        def inner(*args):
            blocks = tuple(jax.tree.map(lambda a: a[0], x)
                           for x in args[:n_shard])
            out = fn(*blocks, *args[n_shard:])
            return jax.tree.map(lambda a: a[None], out)

        in_specs = tuple(spec for _ in sharded) + tuple(P() for _ in replicated)
        # per-machine programs are written for vmap semantics: replicated
        # inputs (support set, hyperparameters) meet block-varying ones in
        # one op, Pallas kernels included (their output shapes declare no
        # varying axes), so shard_map's varying-axes typing is off
        return jax.shard_map(inner, mesh=self.mesh, in_specs=in_specs,
                             out_specs=spec, check_vma=False)(
                                 *sharded, *replicated)


def pad_blocks(X: jax.Array, M: int) -> tuple[jax.Array, int]:
    """(n, ...) -> ((M, ceil(n/M), ...), n): zero-pad to the block layout.

    For *query* batches only: query rows are independent in every predictive
    equation, so padded rows produce garbage predictions for themselves and
    affect nothing else — callers slice outputs back to the returned valid
    count ``n``. (Training data must not be padded; see Runner.shard_blocks.)
    """
    n = X.shape[0]
    b = -(-n // M)                    # ceil(n / M)
    pad = M * b - n
    if pad:
        widths = [(0, pad)] + [(0, 0)] * (X.ndim - 1)
        X = jnp.pad(X, widths)
    return X.reshape((M, b) + X.shape[1:]), n


def scatter_by_block(X: jax.Array, assign: jax.Array, M: int):
    """Scatter (n, ...) rows into an (M, n, ...) block layout by assignment.

    The routed-serving counterpart of ``pad_blocks``: instead of slicing the
    batch positionally, row i lands in block ``assign[i]`` at the next free
    slot (original order preserved within a block — stable sort). Capacity is
    ``n`` per block, so the output shape depends only on (n, M): any
    composition of the same-sized batch compiles to the same executable, and
    a fully-skewed batch (all rows on one block) still fits. Unoccupied slots
    stay zero; per-row independence of the predictive equations makes them
    inert (see ``pad_blocks``).

    Returns ``(Xb, order, block_of, slot)`` where ``Xb[block_of[j], slot[j]]
    == X[order[j]]``; pass the triple to ``gather_by_block`` to restore
    caller order.
    """
    n = X.shape[0]
    order = jnp.argsort(assign, stable=True)               # group by block
    block_of = assign[order]                               # (n,) sorted ids
    starts = jnp.searchsorted(block_of, jnp.arange(M))     # first row of m
    slot = jnp.arange(n) - starts[block_of]                # intra-block slot
    Xb = jnp.zeros((M, n) + X.shape[1:], X.dtype)
    Xb = Xb.at[block_of, slot].set(X[order])
    return Xb, order, block_of, slot


def gather_by_block(vals: jax.Array, order: jax.Array, block_of: jax.Array,
                    slot: jax.Array) -> jax.Array:
    """Invert ``scatter_by_block`` on per-row outputs: (M, n, ...) -> (n, ...)
    in the original caller order."""
    picked = vals[block_of, slot]                          # sorted order
    out = jnp.zeros_like(picked)
    return out.at[order].set(picked)


# ---------------------------------------------------------------------------
# Two-bucket routed scatter: capacity-bounded main bucket + skew overflow.
#
# ``scatter_by_block``'s capacity-n layout is shape-stable and skew-proof but
# computes M*n rows to serve n queries — an M x compute overhead for balanced
# traffic. The two-bucket scheme keeps both properties at ~(1 + 1/alpha) x:
#
#   * main bucket    — (M, cap) per-block layout with cap = alpha*ceil(n/M):
#     each block keeps its first cap routed rows (stable order);
#   * overflow bucket — (G, cap) groups for the rows a skewed batch pushes
#     past a block's capacity. Rows are packed positionally into groups, one
#     BLOCK per group (block m's overflow fills ceil/cap groups exclusively),
#     and each group records the block id whose cached factors serve it —
#     the caller gathers that block's state fields per group, so an overflow
#     row computes the SAME per-row program as the capacity-n layout
#     (bitwise: every predictive equation is row-independent).
#
# G is static: blocks that overflow hold > cap >= alpha*n/M rows, so at most
# n/cap <= M/alpha blocks overflow, and sum_m ceil(o_m/cap) <= n/cap, giving
# G = ceil(M/alpha). Total padded rows: (M + G)*cap ~ (alpha + 1)*n versus
# M*n — at M=8, alpha=2 that is 3n vs 8n (the >= 2x reduction gate in
# benchmarks/bench_serve_latency.py). When cap >= n no row can overflow and
# the overflow bucket is dropped entirely (G = 0).
# ---------------------------------------------------------------------------

ROUTED_ALPHA = 2   # main-bucket capacity multiplier alpha (headroom vs skew)


class RoutedLayout(NamedTuple):
    """Two-bucket scatter result + the bookkeeping to invert it.

    ``Xb[block_of[j], rank[j]] == X[order[j]]`` for main rows
    (``in_main[j]``); overflow row j sits at ``Xo[group[j], slot_o[j]]`` and
    must be served with block ``block_of[j]``'s factors (= ``o_blk`` of its
    group). Pass per-row outputs to ``gather_two_bucket``.
    """
    Xb: jax.Array              # (M, cap, ...) main routed bucket
    Xo: jax.Array | None       # (G, cap, ...) overflow groups (None: G == 0)
    o_blk: jax.Array | None    # (G,) block id served by each overflow group
    order: jax.Array           # (n,) argsort(assign), stable
    block_of: jax.Array        # (n,) assignment in sorted order
    rank: jax.Array            # (n,) intra-block arrival rank
    group: jax.Array           # (n,) overflow group per row (junk if in_main)
    slot_o: jax.Array          # (n,) slot within the overflow group
    in_main: jax.Array         # (n,) bool: row landed in the main bucket

    @property
    def padded_rows(self) -> int:
        """Total computed rows (both buckets) — the compute the layout pays."""
        go = 0 if self.Xo is None else self.Xo.shape[0]
        return (self.Xb.shape[0] + go) * self.Xb.shape[1]


def routed_capacity(n: int, M: int, *, alpha: int = ROUTED_ALPHA,
                    tile: int = 1,
                    max_groups: int | None = None) -> tuple[int, int]:
    """(cap, G) of the two-bucket layout — static given (n, M, alpha).

    ``tile`` rounds cap up to a hardware tile multiple (the Pallas serving
    kernel's block_q), so the per-group query buffers need no second pad
    inside the kernel dispatch.

    ``max_groups`` overrides the worst-case overflow-group count with a
    SMALLER program (lazy overflow dispatch): a caller that knows the
    actual per-block occupancy — the routed ServePlan computes it host-side
    per flush — can run the G=0 program on balanced traffic, or a 1-2 group
    program on mild skew, instead of always paying for ceil(M/alpha)
    groups. The caller owns the sufficiency contract: rows past the
    declared groups' capacity are silently dropped by the scatter (jit-safe
    OOB-drop semantics), so the count AND the assignment driving the
    scatter must come from one float path (ppic.PICServePlan passes its
    host assignment into the program for exactly this reason). Values above
    the worst case are clamped (extra groups could never be occupied)."""
    cap = min(alpha * (-(-n // M)), n)
    cap = -(-cap // tile) * tile
    G = 0 if cap >= n else -(-M // alpha)
    if max_groups is not None:
        G = min(G, max_groups)
    return cap, G


def scatter_two_bucket(X: jax.Array, assign: jax.Array, M: int, *,
                       alpha: int = ROUTED_ALPHA, tile: int = 1,
                       max_groups: int | None = None) -> RoutedLayout:
    """Scatter (n, ...) rows into the two-bucket routed layout by assignment.

    Shape-stable: every array depends only on (n, M, alpha, tile,
    max_groups), so any composition of a same-sized batch reuses the
    compiled executable — the property that makes routed serving
    jit-friendly (see scatter_by_block). Unoccupied slots stay zero;
    per-row independence of the predictive equations makes them inert (see
    ``pad_blocks``). ``max_groups`` selects a smaller overflow program (see
    ``routed_capacity``); the caller guarantees it covers the actual
    overflow, otherwise rows are dropped.
    """
    n = X.shape[0]
    cap, G = routed_capacity(n, M, alpha=alpha, tile=tile,
                             max_groups=max_groups)
    order = jnp.argsort(assign, stable=True)               # group by block
    block_of = assign[order]                               # (n,) sorted ids
    starts = jnp.searchsorted(block_of, jnp.arange(M + 1))
    counts = jnp.diff(starts)                              # (M,) block loads
    rank = jnp.arange(n) - starts[block_of]                # intra-block rank
    in_main = rank < cap

    Xb = jnp.zeros((M, cap) + X.shape[1:], X.dtype)
    Xb = Xb.at[jnp.where(in_main, block_of, M),
               jnp.where(in_main, rank, 0)].set(X[order], mode="drop")

    if G == 0:
        zero = jnp.zeros((n,), jnp.int32)
        return RoutedLayout(Xb, None, None, order, block_of, rank,
                            zero, zero, in_main)

    # overflow: block m's surplus o_m fills ceil(o_m/cap) exclusive groups
    om = jnp.maximum(counts - cap, 0)
    gm = -(-om // cap)                                     # groups per block
    gstart = jnp.cumsum(gm) - gm                           # exclusive prefix
    orank = rank - cap                                     # >= 0 iff overflow
    group = gstart[block_of] + jnp.maximum(orank, 0) // cap
    slot_o = jnp.maximum(orank, 0) % cap
    gi = jnp.where(in_main, G, group)                      # OOB drop for main
    Xo = jnp.zeros((G, cap) + X.shape[1:], X.dtype)
    Xo = Xo.at[gi, jnp.where(in_main, 0, slot_o)].set(X[order], mode="drop")
    o_blk = jnp.zeros((G,), block_of.dtype).at[gi].set(block_of, mode="drop")
    return RoutedLayout(Xb, Xo, o_blk, order, block_of, rank,
                        group, slot_o, in_main)


def gather_two_bucket(vals_main: jax.Array, vals_over: jax.Array | None,
                      lay: RoutedLayout) -> jax.Array:
    """Invert ``scatter_two_bucket`` on per-row outputs: (M, cap, ...) +
    (G, cap, ...) -> (n, ...) in the original caller order."""
    picked = vals_main[lay.block_of, jnp.minimum(lay.rank,
                                                 vals_main.shape[1] - 1)]
    if vals_over is not None:
        over = vals_over[jnp.minimum(lay.group, vals_over.shape[0] - 1),
                         lay.slot_o]
        cond = lay.in_main.reshape((-1,) + (1,) * (picked.ndim - 1))
        picked = jnp.where(cond, picked, over)
    out = jnp.zeros_like(picked)
    return out.at[lay.order].set(picked)


def make_runner(mode: str, *, M: int | None = None, mesh: Mesh | None = None,
                axis_name="machines") -> Runner:
    if mode == "vmap":
        return VmapRunner(M=M, axis_name=axis_name)
    if mode == "shard_map":
        return ShardMapRunner(mesh=mesh, axis_name=axis_name)
    raise ValueError(f"unknown runner mode {mode!r}")
