"""The plain reference, which holds one block at a time, against the
stacked computation it replaced (kept here as a copy): the same answers
for pPITC and pPIC, and compiled programs whose bytes do not grow with the
number of blocks at a fixed block size."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.scipy.linalg import cho_solve, solve_triangular

from bench import check, data, reference
from bench.reference import _chol, _global_terms, kernel, mm

F32 = 4


@pytest.fixture
def float32():
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


# -- the stacked reference as it was: every block's V, C_L and C⁻¹y kept ------

def _stacked_block(params, S, L, noise, p):
    def one(a):
        Xm, ym = a
        V = solve_triangular(L, kernel(params, S, Xm, p), lower=True)
        C_L = _chol(kernel(params, Xm, Xm, p)
                    + noise * jnp.eye(Xm.shape[0]) - mm(V.T, V, p))
        G = solve_triangular(C_L, V.T, lower=True).T
        e = solve_triangular(C_L, ym, lower=True)
        Wy = solve_triangular(C_L, e, lower=True, trans=1)
        return V, C_L, Wy, mm(G, G.T, p), mm(G, e, p)
    return one


@functools.partial(jax.jit, static_argnums=3)
def stacked_summaries(params, S, blocks, precision):
    with jax.default_matmul_precision(precision):
        noise = jnp.exp(2.0 * params["log_noise"])
        L = _chol(kernel(params, S, S, precision))
        per = [jax.lax.map(_stacked_block(params, S, L, noise, precision),
                           (Xb, yb)) for Xb, yb in blocks]
        A = jnp.eye(S.shape[0]) + sum(jnp.sum(p[3], 0) for p in per)
        V, C_L, Wy = per[0][:3]
        return dict(L=L, A_L=jnp.linalg.cholesky(A),
                    c=sum(jnp.sum(p[4], 0) for p in per), V=V, C_L=C_L,
                    Wy=Wy)


@functools.partial(jax.jit, static_argnums=5)
def _stacked_pic(params, S, Xb, ref, Ub, precision):
    sig2 = jnp.exp(2.0 * params["log_signal"])
    p = precision

    def block(a):
        Xm, V, C_L, Wy, Um = a
        v = solve_triangular(ref["L"], kernel(params, S, Um, p), lower=True)
        R = kernel(params, Um, Xm, p) - mm(v.T, V, p)
        P = cho_solve((C_L, True), R.T).T
        z, c = _global_terms(ref, v - mm(V, P.T, p))
        return (mm(z.T, c, p) + mm(R, Wy, p),
                sig2 - jnp.sum(v * v, 0) - jnp.sum(R * P, 1)
                + jnp.sum(z * z, 0))

    with jax.default_matmul_precision(precision):
        return jax.lax.map(block, (Xb, ref["V"], ref["C_L"], ref["Wy"],
                                   Ub))


def stacked_pic(params, S, Xb, ref, U, assign, precision):
    U, assign = np.asarray(U), np.asarray(assign)
    M = int(Xb.shape[0])
    width = max(int(np.bincount(assign, minlength=M).max()), 1)
    Ub = np.zeros((M, width, U.shape[1]), U.dtype)
    slot = np.zeros(U.shape[0], np.int64)
    for m in range(M):
        rows = np.flatnonzero(assign == m)
        Ub[m, :rows.size] = U[rows]
        slot[rows] = np.arange(rows.size)
    means, vars_ = _stacked_pic(params, S, Xb, ref, jnp.asarray(Ub),
                                precision)
    return np.asarray(means)[assign, slot], np.asarray(vars_)[assign, slot]


# -- inputs ---------------------------------------------------------------------

def inputs(domain: str, M: int, b: int, s: int = 32, pool: int = 96,
           seed: int = 2**31 + 5):
    X, y, U = data.draw(data.key_for(seed), domain=domain, n=M * b,
                        n_pool=pool)
    d = X.shape[1]
    S = X[::M * b // s][:s]
    return (data.hyperparameters(domain), S,
            (X.reshape(M, b, d), y.reshape(M, b)), U)


def agree(got, want) -> None:
    for g, w in zip(got, want):
        assert check.rel_err(g, w) <= 1e-6, check.rel_err(g, w)


@pytest.mark.parametrize("precision", [reference.PRECISION,
                                       reference.CONTROL])
def test_ppitc_reference_agrees_with_the_stacked_one(precision, float32):
    params, S, blocks, U = inputs("sarcos", M=4, b=80)
    Xw, yw = blocks[0][:, :8], blocks[1][:, :8]     # a wave's blocks
    for bl in ([blocks], [blocks, (Xw, yw)]):
        got = reference.pitc(params, S, reference.summaries(
            params, S, bl, precision), U, precision)
        want = reference.pitc(params, S, stacked_summaries(
            params, S, bl, precision), U, precision)
        agree(got, want)


@pytest.mark.parametrize("precision", [reference.PRECISION,
                                       reference.CONTROL])
def test_per_block_pic_agrees_with_the_stacked_one(precision, float32):
    params, S, (Xb, yb), U = inputs("aimpeak", M=4, b=80)
    assign = reference.nearest_block(U, Xb)
    got = reference.pic(params, S, (Xb, yb), reference.summaries(
        params, S, [(Xb, yb)], precision), U, assign, precision)
    want = stacked_pic(params, S, Xb, stacked_summaries(
        params, S, [(Xb, yb)], precision), U, assign, precision)
    agree(got, want)


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_reference_bytes_do_not_grow_with_the_blocks(float32, monkeypatch):
    """At a fixed block size b, the global pass over 8 blocks needs less
    than one b×b factor more than over 2 (its stacked G Gᵀ and inputs grow,
    its factors do not), and pic's per-block program is the same size: the
    stacked reference held all M factors (8 × b² floats here). The
    per-block program's queries are padded to its busiest block's count."""
    b = 256
    seen = {}
    real = reference._pic_block

    def spy(*args):
        seen.setdefault(M, []).append(_bytes(real.lower(*args).compile()))
        return real(*args)

    monkeypatch.setattr(reference, "_pic_block", spy)
    glob = {}
    for M in (2, 8):
        params, S, blocks, U = inputs("aimpeak", M=M, b=b, s=16, pool=32)
        glob[M] = _bytes(reference._summaries.lower(
            params, S, [blocks], reference.PRECISION).compile())
        ref = reference.summaries(params, S, [blocks])
        reference.pic(params, S, blocks, ref, U,
                      reference.nearest_block(U, blocks[0]))
    assert glob[8] - glob[2] < F32 * b * b, glob
    assert max(seen[8]) - max(seen[2]) < F32 * b * b, seen
    assert max(seen[8]) < 4 * F32 * b * b, seen
    stacked = _bytes(stacked_summaries.lower(
        params, S, [blocks], reference.PRECISION).compile())
    assert stacked - glob[8] >= 8 * F32 * b * b
