"""Shared pieces of the serving traffic kinds: the served tenant, the client
loop's records, and the check of every answer against the reference.

The scheduler has no thread of its own: one client thread submits, pumps
deadlines and collects results, as a user of ``TenantScheduler`` does. The
scheduler runs on the benchmark's clock (``time.perf_counter``), so queue
deadlines and the records share one time base.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import check, reference

TENANT = "t0"


class ServedTenant:
    """One fitted deployment admitted to a scheduler, with its query pool."""

    def __init__(self, dep, seed: int, pool: int, spans):
        from repro.serving import TenantScheduler
        self.dep, self.spans = dep, spans
        self.ds = dep.dataset(seed, 0, n_pool=pool)
        self.S = dep.support(self.ds.X)
        model = dep.fit(self.ds.X, self.ds.y, self.S)
        self.sched = TenantScheduler(clock=time.perf_counter)
        self.tenant = dep.admit(self.sched, TENANT, model)
        self.pool = np.asarray(self.ds.U, np.float32)
        self.warm_flushes()

    def warm_flushes(self) -> None:
        """One flush of every size a window can dispatch (1 to max_batch
        queries), collected ticket by ticket: the per-size and per-ticket
        programs of the flush and result path compile here, not in the
        window."""
        top = int(self.tenant.max_batch)
        for u in range(1, top + 1):
            tickets = [self.sched.submit(TENANT, self.pool[i % len(self.pool)])
                       for i in range(u)]
            self.sched.flush(TENANT)
            for t in tickets:
                self.sched.result(TENANT, t)

    # -- calls into the scheduler, each one span -----------------------------

    def submit(self, q: int) -> int:
        with self.spans.span("submit") as info:
            b0 = self.tenant.stats.n_batches
            ticket = self.sched.submit(TENANT, self.pool[q])
            info["tag"] = self.tenant.stats.n_batches - b0
        return ticket

    def pump(self) -> None:
        with self.spans.span("pump") as info:
            b0 = self.tenant.stats.n_batches
            self.sched.pump()
            info["tag"] = self.tenant.stats.n_batches - b0

    def done(self, ticket: int) -> bool:
        return self.sched.done(TENANT, ticket)

    def result(self, ticket: int):
        with self.spans.span("result") as info:
            info["tag"] = ticket
            mean, var = self.sched.result(TENANT, ticket)
        return float(mean), float(var)

    def next_deadline(self) -> float:
        """When the oldest queued query is due to be flushed (inf: empty)."""
        q = self.tenant.queue
        if not q:
            return math.inf
        return q[0][2] + self.tenant.flush_deadline_ms * 1e-3

    def counters(self) -> dict:
        stats = self.tenant.plan.stats
        return {"n_batches": self.tenant.stats.n_batches,
                "n_g0_batches": stats.n_g0_batches,
                "n_routed_batches": stats.n_routed_batches}

    def release(self) -> None:
        """Drop the program's state: the scheduler, plan and fitted model."""
        self.sched = self.tenant = None

    # -- correctness --------------------------------------------------------

    def reference(self, precision: str = reference.PRECISION):
        """(mean, var) of the plain reference at every pool query."""
        dep, params = self.dep, self.dep.params
        Xb, yb = self.ds.blocks(dep.M)
        ref = reference.summaries(params, self.S, [(Xb, yb)], precision)
        if dep.routed:
            assign = reference.nearest_block(self.pool, Xb)
            return reference.pic(params, self.S, Xb, ref, self.pool, assign,
                                 precision)
        mean, var = reference.pitc(params, self.S, ref, self.pool,
                                   precision)
        return np.asarray(mean), np.asarray(var)


class FlushMap:
    """Which flush answered each query: the queries submitted so far that
    have left the tenant's queue belong to the flushes dispatched since the
    last look (one tenant's flush takes its whole queue)."""

    def __init__(self, st: ServedTenant, capacity: int):
        self.st = st
        self.of = np.full(capacity, -1)
        self.base = st.tenant.stats.n_batches
        self.assigned = 0
        self.flushes = 0

    def grow(self) -> None:
        self.of = np.concatenate([self.of, np.full_like(self.of, -1)])

    def update(self, submitted: int) -> None:
        flushes = self.st.tenant.stats.n_batches - self.base
        if flushes > self.flushes:
            upto = submitted - self.st.tenant.pending
            self.of[self.assigned:upto] = flushes - 1
            self.assigned, self.flushes = upto, flushes


def compare(mean, var, ref_mean, ref_var) -> dict:
    """The compared numbers of served (mean, var) against a reference."""
    return {"mean_err": check.rel_err(mean, ref_mean),
            "var_err": check.rel_err(var, ref_var)}


class Answers:
    """What the window served: per query its pool index, due, submit and
    done times (s from the window's start), and the answer."""

    def __init__(self, capacity: int):
        self.n = 0
        self.q = np.zeros(capacity, np.int64)
        self.due = np.full(capacity, np.nan)
        self.sub = np.full(capacity, np.nan)
        self.done = np.full(capacity, np.nan)
        self.mean = np.full(capacity, np.nan)
        self.var = np.full(capacity, np.nan)
        self.ticket = np.zeros(capacity, np.int64)

    FIELDS = ("q", "due", "sub", "done", "mean", "var", "ticket")

    def view(self, name: str) -> np.ndarray:
        return getattr(self, name)[:self.n]

    def grow(self) -> None:
        """Double the capacity (the closed loop's count is not known in
        advance)."""
        for name in self.FIELDS:
            a = getattr(self, name)
            fill = 0 if a.dtype.kind == "i" else np.nan
            setattr(self, name, np.concatenate([a, np.full_like(a, fill)]))

    def readings(self, ref_mean, ref_var) -> dict:
        """The compared numbers: every answered query against the reference
        at its pool point. An unanswered query makes the answer not finite,
        so it fails the check."""
        q = self.view("q")
        return compare(self.view("mean"), self.view("var"), ref_mean[q],
                       ref_var[q])

    def unanswered(self) -> int:
        return int(np.sum(~np.isfinite(self.view("done"))))


def host_ms_per_flush(spans, layer: dict) -> np.ndarray:
    """Per flush of the window, the host ms of its scheduler calls: the
    submit or pump span that dispatched it, plus the result spans of the
    queries it answered."""
    of, ans = layer["flush_of"], layer["answers"]
    n = int(of.max()) + 1 if of.size and of.max() >= 0 else 0
    ms = np.zeros(n)
    k = 0
    for name, a, b, tag in spans.records:
        if name in ("submit", "pump") and tag:
            if k < n:
                ms[k] += (b - a) * 1e3
            k += tag
    query_of = {int(t): j for j, t in enumerate(ans.view("ticket"))}
    for a, b, ticket in spans.named("result"):
        j = query_of.get(int(ticket))
        if j is not None and of[j] >= 0:
            ms[of[j]] += (b - a) * 1e3
    return ms


def flush_buckets(traffic) -> list[int]:
    """Rows each flush of the window dispatched: its queries padded to the
    plan's bucket."""
    of = traffic.layer["flush_of"][:traffic.answers.n]
    sizes = np.bincount(of[of >= 0]) if (of >= 0).any() else []
    plan = traffic.st.tenant.plan
    return [plan.bucket_for(int(u)) for u in sizes if u > 0]
