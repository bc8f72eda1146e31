"""Seeded inputs of a cell, made on the device in one jitted call.

The generator is the paper-shaped synthetic one of ``repro.data.synthetic``
(copied, so that the benchmark's inputs do not move with the program): a
smooth random function drawn from an SE-kernel GP by random Fourier
features, inputs uniform on [-2, 2]^d, outputs standardised (zero mean, unit
spread) plus Gaussian noise. ``aimpeak`` (d = 5, traffic speeds) and
``sarcos`` (d = 21, robot-arm torques) differ in dimension, lengthscale and
noise, as in the paper's Sec. 6 (the real files are not in the repository).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# generator settings per domain: (input dim, lengthscale, noise)
DOMAINS = {
    "aimpeak": (5, 1.2, 0.3),
    "sarcos": (21, 4.5, 0.25),
}
RFF_FEATURES = 512


def key_for(seed: int, *salt: int) -> jax.Array:
    """A PRNG key from any whole seed (also past 32 bits) and salts."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


def host_rng(seed: int, *salt: int) -> np.random.Generator:
    """A NumPy generator from the same seed and salts."""
    return np.random.default_rng([int(seed) & (2**63 - 1), *salt])


@functools.partial(jax.jit, static_argnames=("domain", "n", "n_pool"))
def draw(key, *, domain: str, n: int, n_pool: int):
    """(X, y) of n training rows and U, n_pool held-out query inputs, all
    from one random function: standardised outputs with the domain's
    noise."""
    d, ls, noise = DOMAINS[domain]
    kw, kb, ka, kx, ku, ke = jax.random.split(key, 6)
    f32 = jnp.float32
    W = jax.random.normal(kw, (RFF_FEATURES, d), f32) / ls
    b = jax.random.uniform(kb, (RFF_FEATURES,), f32, maxval=2 * math.pi)
    a = jax.random.normal(ka, (RFF_FEATURES,), f32)
    X = jax.random.uniform(kx, (n, d), f32, minval=-2.0, maxval=2.0)
    U = jax.random.uniform(ku, (n_pool, d), f32, minval=-2.0, maxval=2.0)
    XU = jnp.concatenate([X, U])
    phi = (jnp.cos(jnp.dot(XU, W.T, precision="highest") + b)
           * math.sqrt(2.0 / RFF_FEATURES))
    f = jnp.dot(phi, a, precision="highest")
    f = (f - f.mean()) / (f.std() + 1e-9)
    yall = f + noise * jax.random.normal(ke, (n + n_pool,), f32)
    return X, yall[:n], U


def hyperparameters(domain: str) -> dict:
    """The generator's own SE-ARD hyperparameters (signal 1, its noise
    and lengthscale), in the program's parameter layout."""
    d, ls, noise = DOMAINS[domain]
    return {"log_signal": jnp.asarray(0.0, jnp.float32),
            "log_noise": jnp.asarray(math.log(noise), jnp.float32),
            "log_lengthscale": jnp.full((d,), math.log(ls), jnp.float32)}
