"""A configuration's deployment built through the program's own entry points.

The configuration file (``bench/configs/<name>.json``) states the method,
the data scale (|D|, M, |S|), the kernel path and the serving policy; this
module turns it into the calls a user of the program makes: ``api.fit`` /
``api.init_store`` over the cell's runner, a ``ServeSpec``, and a tenant of a
``TenantScheduler``. These are the only ``repro`` entry points the
benchmark uses, beside the plan counters its metrics read.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import data


@dataclasses.dataclass
class Dataset:
    """One seeded draw: training rows in block order, extra rows (waves),
    and a held-out query pool."""
    X: jax.Array          # (n, d), block-major: block m is rows m*b:(m+1)*b
    y: jax.Array          # (n,)
    X_extra: jax.Array    # (n_extra, d)
    y_extra: jax.Array    # (n_extra,)
    U: np.ndarray         # (n_pool, d) held-out query pool, host copy

    def blocks(self, M: int):
        d = self.X.shape[1]
        return self.X.reshape(M, -1, d), self.y.reshape(M, -1)


class Deployment:
    """The system under test for one configuration on ``chips`` chips: on
    one, the M blocks as one vmapped program (``VmapRunner``); on more, one
    block per chip through ``ShardMapRunner`` over a one-axis mesh of the
    first ``chips`` devices, which needs M == chips."""

    def __init__(self, cfg: dict, chips: int = 1):
        from repro.core import covariance as cov
        self.cfg = cfg
        self.M = int(cfg["machines"])
        self.devices = jax.devices()[:chips]
        self.params = data.hyperparameters(cfg["domain"])
        self.kfn = cov.make_spec(cfg["kernel"], impl=cfg["kernel_impl"])
        self.runner = self._runner(chips)

    def _runner(self, chips: int):
        from repro.parallel.runner import ShardMapRunner, VmapRunner
        if chips == 1:
            return VmapRunner(M=self.M)
        if self.M != chips:
            raise ValueError(
                f"a {chips}-chip cell runs one block per chip "
                f"(ShardMapRunner), so its configuration needs machines = "
                f"{chips}; {self.cfg.get('name', 'this configuration')} "
                f"has machines = {self.M}")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((chips,), ("data",), devices=self.devices)
        return ShardMapRunner(mesh=mesh, axis_name="data")

    # -- inputs ---------------------------------------------------------------

    def dataset(self, seed: int, index: int, *, n_extra: int = 0,
                n_pool: int = 0) -> Dataset:
        """Draw ``index`` of the seed: |D| rows (co-clustered into the M
        blocks where the configuration says so), ``n_extra`` further rows
        of the same function, and a held-out pool of ``n_pool`` queries."""
        from repro.core import clustering
        n = int(self.cfg["n"])
        X, y, U = data.draw(data.key_for(seed, index),
                            domain=self.cfg["domain"], n=n + n_extra,
                            n_pool=max(n_pool, 1))
        Xd, yd = X[:n], y[:n]
        if self.cfg["cocluster"]:
            Xn, yn = np.asarray(Xd), np.asarray(yd)
            Xc, yc, _, _, _ = clustering.cocluster(
                Xn, yn, Xn[:self.M], self.M, data.key_for(seed, index, 1))
            Xd, yd = jnp.asarray(Xc), jnp.asarray(yc)
        return Dataset(Xd, yd, X[n:], y[n:], np.asarray(U[:n_pool]))

    def support(self, X) -> jax.Array:
        """|S| support inputs chosen greedily from X (the program's
        selection, an input of the fit)."""
        from repro.core import covariance as cov
        from repro.core import support
        return support.select_support(cov.make_kernel(self.cfg["kernel"]),
                                      self.params, X,
                                      int(self.cfg["support"]))

    # -- the program ------------------------------------------------------------

    def fit(self, X, y, S):
        from repro.core import api
        return api.fit(self.cfg["method"], self.kfn, self.params, X, y, S=S,
                       runner=self.runner)

    def init_store(self, X, y, S):
        from repro.core import api
        return api.init_store(self.cfg["method"], self.kfn, self.params, X,
                              y, S=S, runner=self.runner)

    def serve_spec(self):
        from repro.core import api
        serve = self.cfg["serve"]
        return api.ServeSpec(routed=bool(serve["routed"]),
                             max_batch=int(serve["max_batch"]))

    def admit(self, sched, tenant: str, model, store=None):
        """Admit ``model`` as a tenant with the configuration's serving
        policy, and compile every program its flushes can select."""
        serve = self.cfg["serve"]
        t = sched.admit(tenant, model, self.serve_spec(), store=store,
                        flush_deadline_ms=float(serve["flush_deadline_ms"]))
        d = int(self.cfg["input_dim"])
        if serve["routed"]:
            t.plan.warmup(d, degraded=False)
        else:
            t.plan.warmup(d)
        return t

    @property
    def routed(self) -> bool:
        return bool(self.cfg["serve"]["routed"])
