"""How a flush hands its answers to its tickets (``TenantScheduler._flush``).

A flush starts one host copy of its batch output and stores each ticket as
its row of that batch. ``result``/``collect`` return host NumPy scalars,
bitwise the ticket's row of one plan dispatch over the flushed batch, and
no device program runs per ticket: the first ``result`` of a flush waits
for the copy, the others read host memory.
"""
import contextlib
from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

from repro.core import api
from repro.parallel.runner import VmapRunner
from repro.serving import TenantScheduler

from helpers import make_problem

# one ladder for every max_batch below, so the flush sizes share buckets
BUCKETS = (8, 64, 128)
DEADLINE_MS = 20.0


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def prob():
    return make_problem(dtype=jnp.float32)


@pytest.fixture(scope="module")
def model(prob):
    return api.fit("ppitc", prob["kfn"], prob["params"], prob["X"],
                   prob["y"], S=prob["S"], runner=VmapRunner(M=prob["M"]))


def _points(prob, u, seed=0):
    d = prob["X"].shape[1]
    return np.random.RandomState(seed).randn(u, d).astype(np.float32)


def _tenant(model, *, max_batch=64, **kw):
    clk = Clock()
    sched = TenantScheduler(clock=clk)
    t = sched.admit("t", model,
                    api.ServeSpec(max_batch=max_batch, buckets=BUCKETS),
                    flush_deadline_ms=DEADLINE_MS, **kw)
    return sched, t, clk


def _flush(sched, clk, U, trigger="manual"):
    """Submit U's rows and flush them as one batch by ``trigger`` (a size
    flush fires on the last submit); returns the tickets."""
    tickets = [sched.submit("t", x) for x in U]
    if trigger == "deadline":
        clk.t += 2 * DEADLINE_MS * 1e-3
        assert sched.pump() == len(U)
    elif trigger == "manual":
        assert sched.flush("t") == len(U)
    assert sched.dispatch_log[-1] == ("t", trigger, len(U))
    return tickets


def _assert_rows(got, mean, var, i):
    """``got`` is (mean, var[, degraded]) of row i, as host float32
    scalars bitwise equal to the batch's row."""
    m, v = got[0], got[1]
    assert type(m) is np.float32 and type(v) is np.float32
    assert m.tobytes() == mean[i].tobytes()
    assert v.tobytes() == var[i].tobytes()


@contextlib.contextmanager
def _eager_ops():
    """Names of the primitives dispatched eagerly (each its own device
    program) while the context is open; a jitted call's body is not
    counted."""
    names = []
    run = jax_core.EvalTrace.process_primitive

    def counting(self, primitive, args, params):
        names.append(primitive.name)
        return run(self, primitive, args, params)

    with mock.patch.object(jax_core.EvalTrace, "process_primitive",
                           counting):
        yield names


@pytest.mark.parametrize("trigger", ["size", "deadline", "manual"])
@pytest.mark.parametrize("u", [1, 7, 63, 64])
def test_answers_are_rows_of_one_batch_dispatch(model, prob, u, trigger):
    sched, t, clk = _tenant(model,
                            max_batch=u if trigger == "size" else 128)
    U = _points(prob, u, seed=u)
    tickets = _flush(sched, clk, U, trigger)
    mean, var = (np.asarray(a) for a in t.plan.diag(U))
    for i, tk in enumerate(tickets):
        if i % 2:
            got = sched.collect("t", tk)
            assert got[2] is False
        else:
            got = sched.result("t", tk)
        _assert_rows(got, mean, var, i)
        assert not sched.done("t", tk)
    assert not t.ready and not t.ready_degraded


def test_a_flush_runs_no_device_program_per_ticket(model, prob):
    sched, _, clk = _tenant(model)
    ops = {}
    for u in (9, 40):                    # one bucket (64): one executable
        U = _points(prob, u, seed=u)
        for tk in _flush(sched, clk, U):         # warm this flush size
            sched.result("t", tk)
        with _eager_ops() as in_flush:
            tickets = _flush(sched, clk, U)
        with _eager_ops() as in_results:
            for tk in tickets:
                sched.result("t", tk)
        assert in_results == []
        ops[u] = in_flush
    assert ops[9] == ops[40]


def test_eviction_beyond_max_ready_keeps_the_rows_it_holds(model, prob):
    sched, t, clk = _tenant(model, max_ready=5)
    U1, U2 = _points(prob, 8, seed=1), _points(prob, 4, seed=2)
    first = _flush(sched, clk, U1)
    assert t.stats.n_evicted == 3
    assert [sched.done("t", tk) for tk in first] == [False] * 3 + [True] * 5
    second = _flush(sched, clk, U2)      # evicts 4 more of the first flush
    assert t.stats.n_evicted == 7
    assert list(t.ready) == first[7:] + second
    for tk in first[:7]:
        with pytest.raises(KeyError, match="evicted"):
            sched.result("t", tk)
    m1, v1 = (np.asarray(a) for a in t.plan.diag(U1))
    m2, v2 = (np.asarray(a) for a in t.plan.diag(U2))
    _assert_rows(sched.result("t", first[7]), m1, v1, 7)
    for i, tk in enumerate(second):
        _assert_rows(sched.result("t", tk), m2, v2, i)


def test_sync_waits_for_the_flushes_its_tickets_hold(model, prob):
    sched = TenantScheduler(clock=Clock())
    spec = api.ServeSpec(max_batch=64, buckets=BUCKETS)
    ta, tb = sched.admit("a", model, spec), sched.admit("b", model, spec)
    U = _points(prob, 6)
    tickets = {}
    for tid in ("a", "b"):
        tickets[tid] = [sched.submit(tid, x) for x in U]
        sched.flush(tid)
    sched.sync("a")
    assert all(m.is_ready() and v.is_ready()
               for m, v, _ in ta.ready.values())
    sched.sync()
    assert all(m.is_ready() and v.is_ready()
               for m, v, _ in tb.ready.values())
    mean, var = (np.asarray(a) for a in ta.plan.diag(U))
    with _eager_ops() as ops:
        for tid in ("a", "b"):
            for i, tk in enumerate(tickets[tid]):
                _assert_rows(sched.result(tid, tk), mean, var, i)
    assert ops == []
    sched.sync()                         # nothing uncollected: a no-op


def test_health_path_serves_its_numpy_batches(prob):
    pic = api.fit("ppic", prob["kfn"], prob["params"], prob["X"], prob["y"],
                  S=prob["S"], runner=VmapRunner(M=prob["M"]))
    sched = TenantScheduler(clock=Clock())
    t = sched.admit("t", pic, api.ServeSpec(max_batch=8, routed=True),
                    health=True)
    U = _points(prob, 7, seed=3)
    tickets = [sched.submit("t", x) for x in U]
    sched.flush("t")
    for m, v, _ in t.ready.values():             # health materializes
        assert isinstance(m, np.ndarray) and isinstance(v, np.ndarray)
    mean, var = (np.asarray(a) for a in t.plan.routed_diag(
        U, block_alive=t.health.alive_mask()))
    for i, tk in enumerate(tickets):
        got = sched.collect("t", tk)
        assert got[2] is False
        _assert_rows(got, mean, var, i)
