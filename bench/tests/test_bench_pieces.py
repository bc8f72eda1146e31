"""The benchmark's pieces that need no program run: traffic generation, the
end-to-end statistics, the op and byte counts, the peaks table and the
reduction of a recorded trace."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import check, flops, peaks, serving, traces
from bench.traffic import closed, fit, poisson, waves

DATA = Path(__file__).resolve().parent / "data"
BIG_SEED = 2**31 + 12345


# -- traffic ------------------------------------------------------------------

def test_poisson_arrivals_are_deterministic_per_seed():
    a = poisson.arrivals(BIG_SEED, 300.0, 10.0, 4096)
    b = poisson.arrivals(BIG_SEED, 300.0, 10.0, 4096)
    c = poisson.arrivals(BIG_SEED + 1, 300.0, 10.0, 4096)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("rate", [50.0, 300.0, 2000.0])
def test_poisson_rate_and_gaps(rate):
    seconds = 20.0
    due, q = poisson.arrivals(7, rate, seconds, 4096)
    assert due.shape[0] == round(rate * seconds)   # the same work every seed
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < seconds
    gaps = np.diff(due)
    # exponential gaps: mean 1/rate, coefficient of variation ~1
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.1)
    assert q.min() >= 0 and q.max() < 4096


def test_seeds_past_32_bits_draw_other_arrivals():
    # the arrivals of two seeds that agree in their low 32 bits differ
    a = poisson.arrivals(5, 100.0, 5.0, 64)[0]
    b = poisson.arrivals(5 + 2**32, 100.0, 5.0, 64)[0]
    assert not np.array_equal(a, b)


# -- end-to-end statistics ------------------------------------------------------

def _open_loop(lat_ms: np.ndarray, seconds: float = 10.0):
    t = poisson.Traffic.__new__(poisson.Traffic)
    ans = serving.Answers(lat_ms.size)
    ans.n = lat_ms.size
    ans.due[:] = np.linspace(0, seconds, lat_ms.size, endpoint=False)
    ans.done[:] = ans.due + lat_ms * 1e-3
    t.answers = ans
    return t


def test_tail_is_over_every_request_and_a_stall_moves_it():
    lat = np.full(10_000, 10.0)
    assert _open_loop(lat).end_to_end()["query_p99_ms"] == pytest.approx(10)
    # one stall of the client loop delays 2% of the queries by 300 ms: a
    # median over chunks would not see it, the tail over all requests does
    stalled = lat.copy()
    stalled[5000:5200] += 300.0
    p99 = _open_loop(stalled).end_to_end()["query_p99_ms"]
    assert p99 == pytest.approx(310.0)


def test_an_unanswered_query_leaves_no_tail():
    lat = np.full(100, 10.0)
    t = _open_loop(lat)
    t.answers.done[3] = np.nan
    assert t.end_to_end() == {}
    assert t.failed == 1


def test_rate_is_over_the_whole_window():
    t = closed.Traffic.__new__(closed.Traffic)
    ans = serving.Answers(3000)
    # 1000 answers/s for 2 s, a 1 s stall, then 1000/s again; 50 answers
    # land after the window and are not counted
    done = np.concatenate([np.linspace(0, 2, 2000, endpoint=False),
                           np.linspace(3, 4, 950, endpoint=False),
                           np.linspace(4.01, 4.5, 50)])
    ans.n = done.size
    ans.done[:ans.n] = done
    t.answers, t.seconds = ans, 4.0
    assert t.end_to_end()["queries_per_s"] == pytest.approx(2950 / 4.0)


@pytest.mark.parametrize("kind,attr,metric", [
    (fit, "fits", "fit_s"), (waves, "waves", "wave_s")])
def test_time_per_step_is_the_window_over_the_steps(kind, attr, metric):
    t = kind.Traffic.__new__(kind.Traffic)
    setattr(t, attr, 7)
    t.elapsed = 31.5
    assert t.end_to_end()[metric] == pytest.approx(4.5)


def test_rel_err_and_judge():
    ref = np.array([1.0, -2.0, 4.0])
    assert check.rel_err(ref, ref) == 0.0
    assert check.rel_err(ref + [0, 0, 0.04], ref) == pytest.approx(0.01)
    assert check.rel_err(np.array([1.0, np.nan, 4.0]), ref) == np.inf
    assert check.rel_err(ref[:2], ref) == np.inf
    ok, table = check.judge({"a": 1e-4, "b": 5e-3}, {"a": 1e-3, "b": 1e-3})
    assert not ok and table["a"] == {"value": 1e-4, "limit": 1e-3}
    ok, _ = check.judge({}, {"a": 1.0})
    assert not ok


# -- op and byte counts ---------------------------------------------------------

def test_rbf_counts_by_hand():
    # 2 x 3 entries, d = 4: 8 cross-term ops + 7 each; inputs 2*4 + 3*4
    # floats, output 6 floats
    assert flops.rbf(2, 3, 4) == (2 * 3 * 15, 4 * (8 + 12 + 6))


def test_ppitc_fit_count_by_hand():
    n, M, s, d = 8, 2, 2, 1          # b = 4
    b = 4
    kernel = lambda p, q: p * q * (2 * d + 7)
    per_block = (kernel(s, b) + s * s * b + kernel(b, b) + 2 * b * b * s
                 + b ** 3 / 3 + b * b * s + 2 * s * s * b + 2 * b * b
                 + 2 * s * b)
    glob = kernel(s, s) + 2 * s ** 3 / 3 + 2 * s * s
    assert flops.ppitc_fit(n, M, s, d) == pytest.approx(M * per_block + glob)
    ops, nbytes = flops.rbf_ppitc_fit(n, M, s, d)
    assert ops == M * (kernel(s, b) + kernel(b, b)) + 2 * kernel(s, s)
    assert nbytes == 4 * (M * ((s + b) * d + s * b + 2 * b * d + b * b)
                          + 2 * (2 * s * d + s * s))


def test_pitc_query_count_by_hand():
    s, d = 3, 2
    assert flops.pitc_query(s, d) == s * (2 * d + 7) + 2 * s * s + 6 * s


# -- peaks ------------------------------------------------------------------------

def test_peaks_known_device():
    p = peaks.for_kind("TPU v5 lite")
    assert p["flops_bf16_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("TPU v99 imaginary")


def test_peaks_table_names_its_source():
    assert "TPU v5e" in json.loads(peaks.TABLE.read_text())["source"]


@pytest.mark.parametrize("metric,ops", [
    ("fit_mfu", lambda c: flops.ppitc_fit(c["n"], c["machines"],
                                          c["support"], c["input_dim"])
     * 270 / 30.0),
    ("serve_mfu.qps", lambda c: flops.pitc_query(c["support"],
                                                 c["input_dim"]) * 23000.0)])
def test_peak_share_is_over_the_cells_chips(metric, ops):
    """A share of the peak divides by every chip of the cell: on four it
    reads a quarter of one chip's reading, which is ops/s over one peak."""
    from types import SimpleNamespace

    from bench import run
    read = run.load_reader(metric)
    cfg = {"n": 32000, "machines": 4, "support": 2048, "input_dim": 5}
    traffic = SimpleNamespace(layer={"fits": 270, "elapsed": 30.0},
                              end_to_end=lambda: {"queries_per_s": 23000.0})
    peak = peaks.for_kind("TPU v5 lite")
    share = {chips: read(run.Context(run.Cell("c", chips, {}, cfg, [], []),
                                     traffic, None, None, peak))
             for chips in (1, 4)}
    assert share[1] == pytest.approx(
        100.0 * ops(cfg) / peak["flops_bf16_per_s"], rel=1e-12)
    assert share[4] == pytest.approx(share[1] / 4, rel=1e-12)


# -- the trace reducer, on a trace recorded on a v5e ------------------------------

TRACE = DATA / "tiny_v5e.xplane.pb"
EXPECT = DATA / "tiny_v5e.expect.json"


def test_trace_reduction_of_a_recorded_trace():
    expect = json.loads(EXPECT.read_text())
    tr = traces.load(str(TRACE))
    assert tr.window_s == pytest.approx(expect["window_s"], rel=1e-9)
    # busy: the union of the op intervals, never more than the window
    assert tr.busy_s == pytest.approx(expect["busy_s"], rel=1e-9)
    assert 0 < tr.busy_s < tr.window_s
    ivs = tr.busy_intervals(0)
    assert all(a < b for a, b in ivs)
    assert all(b1 <= a2 for (_, b1), (a2, _) in zip(ivs, ivs[1:]))
    # a kernel's time is the sum of its events
    assert tr.time_s(traces.RBF_KERNEL) == pytest.approx(
        expect["rbf_s"], rel=1e-9)
    assert tr.time_s(traces.RBF_KERNEL) > 0
    # gaps are named by the host span that covers them
    gaps = tr.idle_gaps()
    assert gaps and all(s > 0 for _, s in gaps)
    assert {n for n, _ in gaps} <= set(expect["gap_names"])
    assert sum(s for _, s in gaps) <= tr.window_s - tr.busy_s + 1e-9


def test_idle_and_roofline_percent():
    class Ctx:
        peaks = {"flops_bf16_per_s": 100.0, "hbm_bytes_per_s": 10.0}

    tr = traces.Trace((0, 10**9), [traces.Event("rbf_pallas.1", 0, 2 * 10**8,
                                                 0)], [], [], 1)
    Ctx.trace = tr
    assert traces.idle_percent(Ctx) == pytest.approx(80.0)
    # 10 ops at 100/s = 0.1 s; 1 byte at 10/s = 0.1 s; kernel 0.2 s -> 50%
    assert traces.roofline_percent(Ctx, traces.RBF_KERNEL, 10.0,
                                   1.0) == pytest.approx(50)
    assert traces.roofline_percent(Ctx, "nothing", 10.0, 1.0) is None
    Ctx.trace = None
    assert traces.idle_percent(Ctx) is None
