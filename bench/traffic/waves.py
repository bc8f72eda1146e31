"""Back-to-back waves of new rows, each assimilated and hot-swapped in.

``params``: ``wave_rows`` (rows per wave, split evenly over the M blocks),
``waves`` (distinct waves drawn in set-up from the same function as the
fitted data) and ``pool`` (held-out queries for the check). The serving
tenant holds the fitted pPITC store; each wave is folded into that store
(``store.assimilate``, a rank-``wave_rows`` update of the whitened global
factor) and the result committed to the tenant (``commit_store``). A wave
is complete when the tenant's new state is ready on the device. Each wave
starts from the fitted store, so every wave does the same work at the same
shapes. The window runs whole waves until ``seconds`` have passed.

A traced run's window is one wave: the rank update runs 400 x 2,048
iterations of a loop whose every op the profiler records, and its device
buffer holds about one wave of them (a longer traced window loses the
later waves' events and would read as idle).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import reference, serving

TENANT = serving.TENANT


class Traffic:
    def __init__(self, dep, params: dict, seed: int, spans):
        self.dep, self.params, self.seed, self.spans = dep, params, seed, spans
        self.rows = int(params["wave_rows"])
        self.k = int(params["waves"])

    def setup(self) -> None:
        from repro.core import api
        from repro.serving import TenantScheduler
        dep = self.dep
        self.ds = dep.dataset(self.seed, 0, n_extra=self.rows * self.k,
                              n_pool=int(self.params["pool"]))
        self.S = dep.support(self.ds.X)
        self.base = dep.init_store(self.ds.X, self.ds.y, self.S)
        model = api.FittedGP(api.get(dep.cfg["method"]), dep.kfn, dep.params,
                             self.base.to_state())
        self.sched = TenantScheduler(clock=time.perf_counter)
        self.tenant = dep.admit(self.sched, TENANT, model, store=self.base)
        self.last = None
        self._wave(self.k - 1)     # compiles every program of a wave

    def _rows(self, w: int):
        sl = slice(w * self.rows, (w + 1) * self.rows)
        return self.ds.X_extra[sl], self.ds.y_extra[sl]

    def _wave(self, w: int) -> None:
        Xw, yw = self._rows(w)
        with self.spans.span("store.assimilate") as info:
            info["tag"] = w
            store = self.base.assimilate(Xw, yw)
            jax.block_until_ready(store.store.A_L)
        with self.spans.span("commit_store") as info:
            info["tag"] = w
            self.sched.commit_store(TENANT, store)
            jax.block_until_ready(self.tenant.plan.state)
        self.last = w

    def window(self, seconds: float) -> None:
        clock = time.perf_counter
        self.waves = 0
        t0 = clock()
        while clock() - t0 < seconds:
            self._wave(self.waves % self.k)
            self.waves += 1
            if self.spans.active:
                break
        self.elapsed = clock() - t0

    @property
    def attempted(self) -> int:
        return self.waves

    @property
    def failed(self) -> int:
        return 0

    def end_to_end(self) -> dict:
        return {"wave_s": self.elapsed / self.waves}

    @property
    def layer(self) -> dict:
        return {"waves": self.waves, "elapsed": self.elapsed}

    def _queries(self) -> np.ndarray:
        """The check's queries: the held-out pool and the last wave's own
        inputs, where the wave moves the posterior most."""
        return np.concatenate([np.asarray(self.ds.U, np.float32),
                               np.asarray(self._rows(self.last)[0],
                                          np.float32)])

    def release(self) -> None:
        """Serve the check's queries from the tenant after the last swap,
        in flushes of the serving bucket, then drop the program's state."""
        U = self._queries()
        top = int(self.dep.cfg["serve"]["max_batch"])
        parts = [self.sched.predict(TENANT, U[i:i + top])
                 for i in range(0, U.shape[0], top)]
        self.served = tuple(np.concatenate([np.asarray(p[j]) for p in parts])
                            for j in (0, 1))
        self.sched = self.tenant = self.base = None

    def _reference(self, precision: str):
        dep = self.dep
        Xw, yw = self._rows(self.last)
        blocks = [self.ds.blocks(dep.M),
                  (Xw.reshape(dep.M, -1, Xw.shape[1]), yw.reshape(dep.M, -1))]
        ref = reference.summaries(dep.params, self.S, blocks, precision)
        mean, var = reference.pitc(dep.params, self.S, ref, self._queries(),
                                   precision)
        return np.asarray(mean), np.asarray(var)

    def readings(self, precision: str = "highest") -> dict:
        return serving.compare(*self.served, *self._reference(precision))

    def control_readings(self) -> dict:
        return serving.compare(*self._reference("high"),
                               *self._reference("highest"))
