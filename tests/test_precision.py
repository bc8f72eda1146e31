"""The GP library pins its own f32 matmul precision.

On TPU an f32 matmul that names no precision runs as one bf16 pass, which
the f32 Cholesky pipelines cannot absorb (the served mean and variance turn
non-finite at |S| = 2,048). The library therefore traces its fit and serve
programs at ``linalg.MATMUL_PRECISION``: every ``dot_general`` in a lowered
fit program and a lowered serve program carries it, whatever precision the
caller has set around the call.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, linalg, ppic, ppitc
from repro.parallel.runner import VmapRunner

from helpers import make_problem

PINNED = f"precision = [{linalg.MATMUL_PRECISION.upper()}, " \
         f"{linalg.MATMUL_PRECISION.upper()}]"
CALLER = [None, "bfloat16"]       # the caller's own default precision


@pytest.fixture(scope="module")
def prob():
    return make_problem(dtype=jnp.float32)


def _assert_pinned(hlo: str) -> None:
    dots = [line for line in hlo.splitlines() if "dot_general" in line]
    assert dots, "the program holds no matmul"
    loose = [line for line in dots if PINNED not in line]
    assert not loose, loose[:3]


def _under(caller, fn):
    if caller is None:
        return fn()
    with jax.default_matmul_precision(caller):
        return fn()


@pytest.mark.parametrize("caller", CALLER)
@pytest.mark.parametrize("fit", [ppitc.fit, ppic.fit], ids=["ppitc", "ppic"])
def test_fit_program_matmuls_are_pinned(prob, fit, caller):
    kfn, params = prob["kfn"], prob["params"]
    program = jax.jit(lambda X, y, S: fit(kfn, params, X, y, S=S,
                                          runner=VmapRunner(M=prob["M"])))
    _assert_pinned(_under(caller, lambda: program.lower(
        prob["X"], prob["y"], prob["S"]).as_text()))


@pytest.mark.parametrize("caller", CALLER)
@pytest.mark.parametrize("method", ["ppitc", "ppic"])
def test_serve_program_matmuls_are_pinned(prob, method, caller):
    model = api.fit(method, prob["kfn"], prob["params"], prob["X"],
                    prob["y"], S=prob["S"], runner=VmapRunner(M=prob["M"]))
    plan = model.plan(api.ServeSpec(routed=method == "ppic", max_batch=16))
    U = np.asarray(prob["U"][:16])
    if method == "ppic":
        assign, g = plan._route(U, U.shape[0])
        lowered = lambda: plan._routed_exec(g).lower(
            plan.params, plan.state, plan.caches, U, assign)
    else:
        lowered = lambda: plan._diag_exec().lower(
            plan.params, plan.state, plan.caches, U)
    _assert_pinned(_under(caller, lambda: lowered().as_text()))
