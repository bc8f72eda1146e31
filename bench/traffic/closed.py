"""A closed loop of clients, each with one query outstanding.

``params``: ``clients`` (queries in flight) and ``pool`` (held-out query
points; each query picks one from the seed). At the window's start every
client submits; whenever an answer returns, that client submits its next
query, until the window ends. ``queries_per_s`` is the number of answers
returned inside the window over its length; queries still in flight at the
end are answered afterwards and checked, but not counted.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import data, serving

DRAIN_S = 60.0


class Traffic:
    def __init__(self, dep, params: dict, seed: int, spans):
        self.dep, self.params, self.seed, self.spans = dep, params, seed, spans
        self.clients = int(params["clients"])

    def setup(self) -> None:
        self.st = serving.ServedTenant(self.dep, self.seed,
                                       int(self.params["pool"]), self.spans)

    def window(self, seconds: float) -> None:
        st = self.st
        rng = data.host_rng(self.seed, 13)
        ans = self.answers = serving.Answers(4 * self.clients)
        self.before = st.counters()
        fmap = self.flush_map = serving.FlushMap(st, 4 * self.clients)
        self.seconds = seconds
        clock = time.perf_counter
        outstanding: collections.deque = collections.deque()
        t0 = clock()

        def submit():
            j = ans.n
            if j == ans.q.shape[0]:
                ans.grow()
                fmap.grow()
            ans.q[j] = rng.integers(0, st.pool.shape[0])
            ans.ticket[j] = st.submit(int(ans.q[j]))
            ans.sub[j] = ans.due[j] = clock() - t0
            ans.n += 1
            outstanding.append(j)
            fmap.update(ans.n)

        for _ in range(self.clients):
            submit()
        while outstanding:
            st.pump()
            fmap.update(ans.n)
            while outstanding and st.done(int(ans.ticket[outstanding[0]])):
                j = outstanding.popleft()
                ans.mean[j], ans.var[j] = st.result(int(ans.ticket[j]))
                ans.done[j] = clock() - t0
                if ans.done[j] < seconds:
                    submit()
            if clock() - t0 > seconds + DRAIN_S:
                break
            if outstanding and not st.done(int(ans.ticket[outstanding[0]])):
                wake = st.next_deadline() - clock()
                if wake > 2e-4:
                    time.sleep(min(wake - 1e-4, 1e-2))
        self.elapsed = clock() - t0
        self.after = st.counters()

    @property
    def attempted(self) -> int:
        return self.answers.n

    @property
    def failed(self) -> int:
        return self.answers.unanswered()

    def end_to_end(self) -> dict:
        done = self.answers.view("done")
        return {"queries_per_s":
                float(np.sum(done <= self.seconds)) / self.seconds}

    @property
    def layer(self) -> dict:
        return {"answers": self.answers, "flush_of": self.flush_map.of,
                "before": self.before, "after": self.after,
                "elapsed": self.elapsed, "seconds": self.seconds}

    def release(self) -> None:
        self.st.release()

    def readings(self, precision: str = "highest") -> dict:
        return self.answers.readings(*self.st.reference(precision))

    def control_readings(self) -> dict:
        ref = self.st.reference()
        low = self.st.reference("high")
        q = self.answers.view("q")
        return serving.compare(low[0][q], low[1][q], ref[0][q], ref[1][q])
