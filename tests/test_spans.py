"""The program's span recorder (``repro.runtime.spans``).

Off (no profiler session), a span records nothing and builds no
``TraceAnnotation``. On, under ``jax.profiler.trace`` with the options the
benchmark traces with, it records parents and self time, compiles under
the span that caused them, counts what a full buffer drops, lands on the
profiler's host plane at a constant offset from its ``perf_counter``
record, and records nothing inside a JAX trace. The scheduler's spans of
one flush join by ticket."""
import importlib.util
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import api
from repro.parallel.runner import VmapRunner
from repro.runtime import spans
from repro.serving import TenantScheduler

from helpers import make_problem

ROOT = Path(__file__).resolve().parents[1]


def _bench_traces():
    """``bench/traces.py``, loaded by path (tests/ does not import the
    benchmark package)."""
    spec = importlib.util.spec_from_file_location(
        "bench_traces_for_spans", ROOT / "bench" / "traces.py")
    module = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TRACES = _bench_traces()


@pytest.fixture
def recorder():
    spans.clear()
    yield spans
    spans.clear()


@pytest.fixture
def traced(tmp_path):
    """A profiler session with the benchmark's options; yields the log
    directory, read after the session ends."""
    def session():
        return jax.profiler.trace(str(tmp_path),
                                  profiler_options=TRACES.options())
    return session, tmp_path


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r.name, []).append(r)
    return out


def test_off_records_nothing_and_builds_no_annotation(recorder, monkeypatch):
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            built.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    for i in range(100):
        with spans.span("test.off", ticket=i):
            with spans.span("test.inner"):
                pass
    jax.jit(lambda x: x + 1)(jnp.arange(3.0))     # a compile, off
    assert spans.records() == [] and spans.dropped() == 0
    assert built == []


def test_parents_and_self_time(recorder, traced):
    session, _ = traced
    with session():
        with spans.span("test.outer", ticket=7):
            time.sleep(0.02)
            with spans.span("test.inner"):
                time.sleep(0.03)
            with spans.span("test.inner"):
                time.sleep(0.01)
    recs = _by_name(spans.records())
    (outer,) = recs["test.outer"]
    inner = recs["test.inner"]
    assert outer.parent == -1 and outer.tag == {"ticket": 7}
    assert [r.parent for r in inner] == [outer.seq, outer.seq]
    assert all(outer.t0 <= r.t0 < r.t1 <= outer.t1 for r in inner)
    own = spans.self_time(spans.records())
    children = sum(r.t1 - r.t0 for r in inner)
    assert own[outer.seq] == pytest.approx(outer.t1 - outer.t0 - children)
    assert 0.015 < own[outer.seq] < 0.05
    assert own[inner[0].seq] == pytest.approx(inner[0].t1 - inner[0].t0)


def test_dropped_at_the_bound(recorder, traced, monkeypatch):
    session, _ = traced
    monkeypatch.setattr(spans, "CAPACITY", 5)
    with session():
        for _ in range(8):
            with spans.span("test.many"):
                pass
    assert len(spans.records()) == 5 and spans.dropped() == 3
    spans.clear()
    assert spans.records() == [] and spans.dropped() == 0


def test_compile_is_recorded_under_its_span(recorder, traced):
    session, _ = traced
    fn = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)
    x = jnp.arange(5.0)
    jax.block_until_ready(x)
    with session():
        with spans.span("test.warm"):
            jax.block_until_ready(fn(x))
        with spans.span("test.again"):
            jax.block_until_ready(fn(x))
    recs = spans.records()
    named = _by_name(recs)
    (warm,) = named["test.warm"]
    (again,) = named["test.again"]
    compiles = named[spans.COMPILE]
    assert compiles and all(c.parent == warm.seq for c in compiles)
    assert all(warm.t0 <= c.t0 and c.t1 <= warm.t1 for c in compiles)
    assert not any(c.parent == again.seq for c in compiles)


def test_nothing_is_recorded_inside_a_jax_trace(recorder, traced):
    session, _ = traced

    @jax.jit
    def f(x):
        with spans.span("test.traced"):
            return x * 2

    with session():
        jax.block_until_ready(jax.vmap(f)(jnp.ones((2, 3))))
    assert "test.traced" not in _by_name(spans.records())


def test_spans_land_on_the_host_plane_at_a_constant_offset(recorder, traced):
    session, log_dir = traced
    with session():
        for i in range(4):
            with spans.span("test.clock", i=i):
                time.sleep(0.01 * (i + 1))
            time.sleep(0.02)
    recs = _by_name(spans.records())["test.clock"]
    pd = ProfileData.from_file(TRACES.find_xplane(str(log_dir)))
    events = sorted((int(e.start_ns), int(e.duration_ns))
                    for plane in pd.planes if plane.name.startswith("/host")
                    for line in plane.lines for e in line.events
                    if e.name == "test.clock")
    assert len(events) == len(recs) == 4
    starts = [s - r.t0 * 1e9 for (s, _), r in zip(events, recs)]
    ends = [s + d - r.t1 * 1e9 for (s, d), r in zip(events, recs)]
    assert max(starts + ends) - min(starts + ends) <= 1e6


def test_no_span_name_takes_the_benchmark_prefix():
    names = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        text = path.read_text()
        names |= {part.split('"')[0]
                  for part in text.split('spans.span("')[1:]}
    assert {"sched.flush", "plan.exec", "fit.ppitc",
            "online.rank_update"} <= names
    assert not any(n.startswith("bench.") for n in names)


# -- the scheduler and the serve plan ------------------------------------------------

@pytest.fixture(scope="module")
def served():
    p = make_problem(dtype=jnp.float32)
    model = api.fit("ppitc", p["kfn"], p["params"], p["X"], p["y"],
                    S=p["S"], runner=VmapRunner(M=p["M"]))
    sched = TenantScheduler()
    tenant = sched.admit("t", model, api.ServeSpec(max_batch=8))
    U = np.asarray(p["U"], np.float32)
    for t in [sched.submit("t", U[i]) for i in range(5)]:   # warm every
        sched.result("t", t)                                # program
    return sched, tenant, U


def test_one_traced_flush_of_five_tickets(recorder, traced, served):
    session, _ = traced
    sched, _, U = served
    with session():
        tickets = [sched.submit("t", U[i]) for i in range(5)]
        sched.flush("t")
        for t in tickets:
            sched.result("t", t)
    recs = spans.records()
    named = _by_name(recs)
    assert len(named["sched.submit"]) == 5
    (flush,) = named["sched.flush"]
    assert flush.tag == {"tenant": "t", "trigger": "manual",
                         "first_ticket": tickets[0], "n_tickets": 5}
    children = {r.name for r in recs if r.parent == flush.seq}
    assert children == {"sched.stack", "sched.dispatch", "sched.unpack"}
    (dispatch,) = named["sched.dispatch"]
    assert {r.name for r in recs if r.parent == dispatch.seq} == {
        "plan.pad", "plan.exec", "plan.trim"}
    # the submits, the flush and the results join by ticket
    submitted = {r.tag["ticket"] for r in named["sched.submit"]}
    took = set(range(flush.tag["first_ticket"],
                     flush.tag["first_ticket"] + flush.tag["n_tickets"]))
    assert submitted == took == set(tickets)
    results = named["sched.result"]
    assert {r.tag["ticket"] for r in results} == set(tickets)
    waits = named["sched.wait"]
    assert sorted(w.parent for w in waits) == sorted(r.seq for r in results)
    assert all(s.t1 <= flush.t0 for s in named["sched.submit"])
    assert spans.COMPILE not in named          # warmed: nothing compiled


def test_serve_executables_are_named_after_their_key(served):
    _, tenant, _ = served
    plan = tenant.plan
    fn = plan._exec["diag"]
    U0 = np.zeros((plan.bucket_for(1), plan.state.S.shape[1]), np.float32)
    text = fn.lower(plan.params, plan.state, plan.caches, U0).as_text()
    assert "@jit_serve_diag" in text
    ladder = plan._jitted(("routed_deg", 2), lambda: lambda *a: a[-1])
    plan._exec.pop(("routed_deg", 2))
    assert ladder.__name__ == "serve_routed_deg_g2"
