"""Multi-device shard_map coverage.

Runs in a subprocess with XLA_FLAGS forcing 8 host devices (the flag must not
leak into this process — smoke tests need the real single device), comparing
every parallel method's shard_map execution against the vmap simulation.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # subprocess spawn + 8-device XLA compile

SCRIPT = r"""
import jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
assert len(jax.devices()) == 8, jax.devices()
from repro.core import covariance as cov, ppitc, ppic, picf, support, hyper
from repro.launch.mesh import make_mesh
from repro.parallel.runner import ShardMapRunner, VmapRunner

mesh = make_mesh((8,), ("data",))
sm = ShardMapRunner(mesh=mesh, axis_name="data")
vm = VmapRunner(M=8)
key = jax.random.PRNGKey(0)
n, u, s, d = 128, 32, 12, 3
X = jax.random.normal(key, (n, d))
S = jax.random.normal(jax.random.PRNGKey(1), (s, d))
U = jax.random.normal(jax.random.PRNGKey(2), (u, d))
params = cov.init_params(d, signal=1.3, noise=0.3, lengthscale=1.5,
                         dtype=jnp.float64)
kfn = cov.make_kernel("se")
y = jnp.sin(X[:, 0]) * 2 + X[:, 1] + 0.1 * jax.random.normal(
    jax.random.PRNGKey(3), (n,))

def close(a, b, tol=1e-10):
    assert float(jnp.abs(a - b).max()) < tol, float(jnp.abs(a - b).max())

a, b = ppitc.predict(kfn, params, S, X, y, U, sm), \
    ppitc.predict(kfn, params, S, X, y, U, vm)
close(a.mean, b.mean); close(a.blocks, b.blocks)
a, b = ppic.predict(kfn, params, S, X, y, U, sm), \
    ppic.predict(kfn, params, S, X, y, U, vm)
close(a.mean, b.mean); close(a.blocks, b.blocks)
a, b = picf.predict(kfn, params, X, y, U, 48, sm), \
    picf.predict(kfn, params, X, y, U, 48, vm)
close(a.mean, b.mean); close(a.cov, b.cov)
a, b = picf.predict(kfn, params, X, y, U, 48, sm, shard_u=True), \
    picf.predict(kfn, params, X, y, U, 48, vm, shard_u=True)
close(a.mean, b.mean); close(a.blocks, b.blocks)
close(support.select_support_parallel(kfn, params, X, 8, sm),
      support.select_support_parallel(kfn, params, X, 8, vm))
close(hyper.pitc_nlml(kfn, params, S, X, y, sm),
      hyper.pitc_nlml(kfn, params, S, X, y, vm), 1e-8)

# fully-collective execution (psum inside the per-machine program)
a, b = ppitc.predict_distributed(kfn, params, S, X, y, U, sm), \
    ppitc.predict_distributed(kfn, params, S, X, y, U, vm)
close(a.mean, b.mean); close(a.blocks, b.blocks)
a, b = ppic.predict_distributed(kfn, params, S, X, y, U, sm), \
    ppic.predict_distributed(kfn, params, S, X, y, U, vm)
close(a.mean, b.mean); close(a.blocks, b.blocks)
a, b = picf.predict_distributed(kfn, params, X, y, U, 48, sm), \
    picf.predict_distributed(kfn, params, X, y, U, 48, vm)
close(a.mean, b.mean); close(a.cov, b.cov)

# PosteriorState round-trip: both runners' fit paths produce the same pytree
import jax.tree_util as jtu
def close_tree(ta, tb, tol=1e-10):
    la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
    assert jtu.tree_structure(ta) == jtu.tree_structure(tb)
    assert len(la) == len(lb)
    for x, z in zip(la, lb):
        close(x, z, tol)
close_tree(ppitc.fit(kfn, params, X, y, S=S, runner=sm),
           ppitc.fit(kfn, params, X, y, S=S, runner=vm))
close_tree(ppic.fit(kfn, params, X, y, S=S, runner=sm),
           ppic.fit(kfn, params, X, y, S=S, runner=vm))
close_tree(picf.fit(kfn, params, X, y, rank=48, runner=sm),
           picf.fit(kfn, params, X, y, rank=48, runner=vm))

# two-axis machines: ("pod", "data") as in the production mesh
mesh2 = make_mesh((2, 4), ("pod", "data"))
sm2 = ShardMapRunner(mesh=mesh2, axis_name=("pod", "data"))
a = ppic.predict(kfn, params, S, X, y, U, sm2)
close(a.mean, ppic.predict(kfn, params, S, X, y, U, vm).mean)
print("SHARD_MAP_OK")
"""


def test_shard_map_matches_vmap_on_8_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARD_MAP_OK" in r.stdout


SERVE_SCRIPT = r"""
import jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
assert len(jax.devices()) == 4, jax.devices()
from repro.core import api, covariance as cov
from repro.launch.mesh import make_mesh
from repro.parallel.runner import ShardMapRunner, VmapRunner

sm = ShardMapRunner(mesh=make_mesh((4,), ("data",)), axis_name="data")
vm = VmapRunner(M=4)
n, u, s, d = 64, 16, 12, 3
X = jax.random.normal(jax.random.PRNGKey(0), (n, d))
S = jax.random.normal(jax.random.PRNGKey(1), (s, d))
U = jax.random.normal(jax.random.PRNGKey(2), (u, d))
y = jnp.sin(X[:, 0]) + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (n,))
params = cov.init_params(d, signal=1.3, noise=0.3, lengthscale=1.5,
                         dtype=jnp.float64)
kfn = cov.make_kernel("se")
pallas = cov.make_spec("se", impl="pallas")

for method, routed in (("ppitc", False), ("ppic", True)):
    a = api.fit(method, kfn, params, X, y, S=S, runner=sm)
    b = api.fit(method, kfn, params, X, y, S=S, runner=vm)
    assert api.state_device_count(a.state) == 4
    assert api.state_device_count(b.state) == 1
    plan = a.plan(api.ServeSpec(kernel=pallas, routed=routed, max_batch=u))
    assert plan.kfn.impl == "jnp", plan.kfn
    assert "4 devices" in plan.kernel_note, plan.kernel_note
    ref = b.plan(api.ServeSpec(routed=routed, max_batch=u))
    assert ref.kernel_note is None
    got = (plan.routed_diag if routed else plan.diag)(U)
    want = (ref.routed_diag if routed else ref.diag)(U)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) < 1e-10, method
    # a plan that serves through Pallas refuses a hot-swap onto the
    # sharded state instead of failing at its next dispatch
    single = b.plan(api.ServeSpec(kernel=pallas, routed=routed))
    try:
        single.rebind(a.state)
        raise AssertionError("rebind onto a 4-device state accepted")
    except ValueError as e:
        assert "4 devices" in str(e), e
print("SHARDED_SERVE_OK")
"""


def test_sharded_state_serves_through_a_pallas_spec():
    """A state fitted by ShardMapRunner spans the mesh's devices; a plan
    asked to serve it with the compiled Pallas kernel builds its
    cross-covariances in XLA instead (a Mosaic call cannot be partitioned
    across devices), says so in ``kernel_note``, and serves what the
    single-device state serves; a Pallas plan refuses a rebind onto it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    r = subprocess.run([sys.executable, "-c", SERVE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED_SERVE_OK" in r.stdout
