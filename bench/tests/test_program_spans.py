"""The readers of the program's own spans: each new per-layer metric on
synthetic records and a synthetic trace, checked by hand; nothing to read
(no records, a buffer that dropped some, a program without spans) reads
None; and the traced small cells on the CPU report every new metric."""
import sys

import jax
import pytest

from bench import program_spans, run, traces
from bench.spans import Spans
from bench.tests.test_bench_cells import go
from repro.runtime import spans

NEW = {"sarcos-ppitc.closed": {"unpack_ms_per_flush.qps",
                               "dispatch_ms_per_flush.qps",
                               "queue_wait_ms.qps"},
       "sarcos-ppitc.fit": {"summarize_idle_ms.fit"},
       "sarcos-ppitc.waves": {"summarize_idle_ms.wave"}}

# the window is [100 s, 101 s] on perf_counter and [5 s, 6 s] on the trace
W0, NS0 = 100.0, 5_000_000_000


def rec(seq, name, t0, t1, parent=-1, **tag):
    return spans.Record(seq, name, W0 + t0, W0 + t1, parent, tag)


CLOSED = [
    rec(0, "sched.flush", -1.0, -0.99, first_ticket=90, n_tickets=1,
        tenant="t"),                                  # before the window
    rec(1, "sched.submit", 0.100, 0.101, tenant="t", ticket=0),
    rec(2, "sched.submit", 0.120, 0.121, tenant="t", ticket=1),
    rec(3, "sched.flush", 0.150, 0.160, tenant="t", first_ticket=0,
        n_tickets=2, trigger="deadline"),
    rec(4, "sched.stack", 0.150, 0.151, 3),
    rec(5, "sched.dispatch", 0.151, 0.154, 3),
    rec(6, "sched.unpack", 0.154, 0.159, 3),
    rec(7, "sched.submit", 0.200, 0.241, tenant="t", ticket=2),
    rec(8, "sched.flush", 0.230, 0.240, 7, tenant="t", first_ticket=2,
        n_tickets=1, trigger="size"),
    rec(9, "sched.dispatch", 0.231, 0.233, 8),
    rec(10, "sched.unpack", 0.233, 0.237, 8),
]

SUMMARIZE = [rec(0, "online.summarize", 0.2, 0.4),
             rec(1, "online.to_state", 0.7, 0.75),
             rec(2, "online.summarize", 0.6, 0.7)]


def ns(s: float) -> int:
    return NS0 + round(s * 1e9)


# device 0 busy on [0.25, 0.30] and [0.35, 0.65] s of the window; device 1's
# op lies in a span and is not device 0's
TRACE = traces.Trace(
    (NS0, NS0 + 10**9),
    [traces.Event("a", ns(0.25), ns(0.30), 0),
     traces.Event("b", ns(0.35), ns(0.65), 0),
     traces.Event("c", ns(0.20), ns(0.40), 1)], [], [], 1)


def ctx_for(records, monkeypatch, *, dropped=0, layer=None):
    monkeypatch.setattr(spans, "records", lambda: list(records))
    monkeypatch.setattr(spans, "dropped", lambda: dropped)
    bench_spans = Spans(True)
    bench_spans.records.append(("window", W0, W0 + 1.0, None))

    class Traffic:
        pass

    traffic = Traffic()
    traffic.layer = layer or {}
    return run.Context(cell=None, traffic=traffic, trace=TRACE,
                       spans=bench_spans, peaks=None)


def test_per_flush_readers_by_hand(monkeypatch):
    ctx = ctx_for(CLOSED, monkeypatch)
    # two flushes in the window: unpack 5 + 4 ms, dispatch 3 + 2 ms
    assert run.load_reader("unpack_ms_per_flush.qps")(ctx) == \
        pytest.approx(4.5)
    assert run.load_reader("dispatch_ms_per_flush.qps")(ctx) == \
        pytest.approx(2.5)
    # tickets 0, 1, 2 waited 150-100, 150-120 and 230-200 ms
    assert run.load_reader("queue_wait_ms.qps")(ctx) == \
        pytest.approx((50 + 30 + 30) / 3)


def test_self_time_and_count_by_hand(monkeypatch):
    ps = program_spans.load(ctx_for(CLOSED, monkeypatch))
    assert ps.count("sched.flush") == 2
    # 10 ms less 1 + 3 + 5 ms, and 10 ms less 2 + 4 ms
    assert ps.self_ms("sched.flush") == pytest.approx(1 + 4)
    # the size flush is a child of its submit
    assert ps.self_ms("sched.submit") == pytest.approx(1 + 1 + 41 - 10)


@pytest.mark.parametrize("metric,layer", [
    ("summarize_idle_ms.fit", {"fits": 3}),
    ("summarize_idle_ms.wave", {"waves": 1})])
def test_idle_under_a_span_by_hand(metric, layer, monkeypatch):
    ctx = ctx_for(SUMMARIZE, monkeypatch, layer=layer)
    # spans [0.2, 0.4] and [0.6, 0.7] s: 300 ms, of which device 0 is busy
    # 50 + 50 + 50 ms; the idle 150 ms over the steps
    steps = layer.get("fits", layer.get("waves"))
    assert run.load_reader(metric)(ctx) == pytest.approx(150.0 / steps)
    ps = program_spans.load(ctx)
    # [0.7, 0.75] s: after device 0's last op
    assert ps.idle_ms_under("online.to_state") == pytest.approx(50.0)


@pytest.mark.parametrize("metric", sorted(set().union(*NEW.values())))
def test_nothing_to_read_reads_none(metric, monkeypatch):
    layer = {"fits": 3, "waves": 1}
    records = CLOSED + [r._replace(seq=r.seq + 100) for r in SUMMARIZE]
    read = run.load_reader(metric)
    assert read(ctx_for(records, monkeypatch, layer=layer)) is not None
    assert read(ctx_for(records, monkeypatch, dropped=1,
                        layer=layer)) is None
    assert read(ctx_for([], monkeypatch, layer=layer)) is None
    # an older program, without the span recorder
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr(sys.modules["repro.runtime"], "spans")
    assert read(ctx_for(records, monkeypatch, layer=layer)) is None


@pytest.fixture
def float32():
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


@pytest.mark.parametrize("name", sorted(NEW))
def test_traced_small_cell_reports_its_new_metrics(name, float32):
    spans.clear()
    r = go(name, trace=True)
    assert r["correct"]
    assert NEW[name] <= set(r["metrics"])
    for metric in NEW[name]:
        assert r["metrics"][metric]["value"] >= 0
    if name == "sarcos-ppitc.closed":
        m = {k: v["value"] for k, v in r["metrics"].items()}
        assert (m["unpack_ms_per_flush.qps"] + m["dispatch_ms_per_flush.qps"]
                <= m["host_ms_per_flush.qps"])
