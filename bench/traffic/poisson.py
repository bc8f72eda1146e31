"""Open-loop Poisson arrivals of single-point queries at a fixed rate.

``params``: ``rate_per_s`` (offered load) and ``pool`` (held-out query
points drawn from the configuration's generator; each query picks one).
A run of ``seconds`` offers exactly round(rate * seconds) queries, their due
times uniform over the window and sorted: a Poisson process given its
count, so every seed offers the same amount of work in another order. Each
query is timed from when it was due to when its ``result`` returned, so a
stall of the client loop counts against the queries it delays.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from bench import data, serving

DRAIN_S = 60.0    # how long past the window an answer may take to come


def arrivals(seed: int, rate: float, seconds: float, pool: int):
    """(due times in s, pool indices) of the window's queries: exactly
    round(rate * seconds) of them, due uniformly over the window, sorted."""
    n = int(round(rate * seconds))
    rng = data.host_rng(seed, 11)
    due = np.sort(rng.uniform(0.0, seconds, n))
    return due, rng.integers(0, pool, n)


class Traffic:
    def __init__(self, dep, params: dict, seed: int, spans):
        self.dep, self.params, self.seed, self.spans = dep, params, seed, spans
        self.rate = float(params["rate_per_s"])

    def setup(self) -> None:
        self.st = serving.ServedTenant(self.dep, self.seed,
                                       int(self.params["pool"]), self.spans)

    def window(self, seconds: float) -> None:
        st = self.st
        due, q = arrivals(self.seed, self.rate, seconds, st.pool.shape[0])
        n = due.shape[0]
        ans = self.answers = serving.Answers(n)
        ans.due[:], ans.q[:], ans.n = due, q, n
        self.before = st.counters()
        fmap = self.flush_map = serving.FlushMap(st, n)
        clock = time.perf_counter
        outstanding: collections.deque = collections.deque()
        i = 0
        t0 = clock()
        give_up = seconds + DRAIN_S
        while True:
            now = clock() - t0
            while i < n and ans.due[i] <= now:
                ans.ticket[i] = st.submit(int(ans.q[i]))
                ans.sub[i] = clock() - t0
                outstanding.append(i)
                i += 1
                fmap.update(i)
                now = clock() - t0
            st.pump()
            fmap.update(i)
            while outstanding and st.done(int(ans.ticket[outstanding[0]])):
                j = outstanding.popleft()
                ans.mean[j], ans.var[j] = st.result(int(ans.ticket[j]))
                ans.done[j] = clock() - t0
            if i == n and not outstanding:
                break
            now = clock() - t0
            if now > give_up:
                break
            wake = min(ans.due[i] if i < n else math.inf,
                       st.next_deadline() - t0)
            if wake - now > 2e-4:
                time.sleep(min(wake - now - 1e-4, 1e-2))
        self.elapsed = clock() - t0
        self.after = st.counters()

    @property
    def attempted(self) -> int:
        return self.answers.n

    @property
    def failed(self) -> int:
        return self.answers.unanswered()

    def latencies_ms(self) -> np.ndarray:
        a = self.answers
        return (a.view("done") - a.view("due")) * 1e3

    def end_to_end(self) -> dict:
        lat = self.latencies_ms()
        if not np.isfinite(lat).all():
            return {}
        return {"query_p99_ms": float(np.percentile(lat, 99))}

    @property
    def layer(self) -> dict:
        return {"answers": self.answers, "flush_of": self.flush_map.of,
                "before": self.before, "after": self.after,
                "elapsed": self.elapsed}

    def release(self) -> None:
        self.st.release()

    def readings(self, precision: str = "highest") -> dict:
        return self.answers.readings(*self.st.reference(precision))

    def control_readings(self) -> dict:
        ref = self.st.reference()
        low = self.st.reference("high")
        q = self.answers.view("q")
        return serving.compare(low[0][q], low[1][q], ref[0][q], ref[1][q])
