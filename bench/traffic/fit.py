"""Back-to-back paper-scale fits, each on the next of a few datasets.

``params``: ``datasets`` (independent draws of the configuration's
generator made in set-up, each with its own random function, so no fit can
reuse an earlier one's result) and ``pool`` (held-out queries the fitted
states are checked on). The support set is chosen once, from the first
dataset. The window runs whole fits until ``seconds`` have passed, so
``fit_s`` is the window's length over the fits in it.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import reference, serving


class Traffic:
    def __init__(self, dep, params: dict, seed: int, spans):
        self.dep, self.params, self.seed, self.spans = dep, params, seed, spans

    def setup(self) -> None:
        dep = self.dep
        k = int(self.params["datasets"])
        self.sets = [dep.dataset(self.seed, i, n_pool=int(self.params["pool"]))
                     for i in range(k)]
        self.S = dep.support(self.sets[0].X)
        self.latest = {}
        self._fit(0)               # compiles every program of a fit

    def _fit(self, i: int):
        ds = self.sets[i]
        with self.spans.span("api.fit") as info:
            info["tag"] = i
            model = self.dep.fit(ds.X, ds.y, self.S)
            jax.block_until_ready(model.state)
        self.latest[i] = model
        return model

    def window(self, seconds: float) -> None:
        clock = time.perf_counter
        self.fits = 0
        t0 = clock()
        while clock() - t0 < seconds:
            self._fit((self.fits + 1) % len(self.sets))
            self.fits += 1
        self.elapsed = clock() - t0

    @property
    def attempted(self) -> int:
        return self.fits

    @property
    def failed(self) -> int:
        return 0

    def end_to_end(self) -> dict:
        return {"fit_s": self.elapsed / self.fits}

    @property
    def layer(self) -> dict:
        return {"fits": self.fits, "elapsed": self.elapsed}

    def release(self) -> None:
        """Keep only what the check reads: each dataset's latest state
        served on its pool."""
        self.served = {}
        for i, model in self.latest.items():
            U = self.sets[i].U
            mean, var = model.predict_diag(U)
            self.served[i] = (np.asarray(mean), np.asarray(var))
        self.latest = None

    def _reference(self, i: int, precision: str):
        dep, ds = self.dep, self.sets[i]
        ref = reference.summaries(dep.params, self.S, [ds.blocks(dep.M)],
                                  precision)
        mean, var = reference.pitc(dep.params, self.S, ref, ds.U, precision)
        return np.asarray(mean), np.asarray(var)

    def readings(self, precision: str = "highest") -> dict:
        """Every dataset's latest fitted state, served on its held-out pool,
        against the reference fitted on the same data; the worst of them."""
        worst = {}
        for i, (mean, var) in self.served.items():
            r = serving.compare(mean, var, *self._reference(i, precision))
            worst = {k: max(v, worst.get(k, 0.0)) for k, v in r.items()}
        return worst

    def control_readings(self) -> dict:
        worst = {}
        for i in self.served:
            low = self._reference(i, "high")
            r = serving.compare(*low, *self._reference(i, "highest"))
            worst = {k: max(v, worst.get(k, 0.0)) for k, v in r.items()}
        return worst
