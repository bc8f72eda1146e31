"""Shared fixtures. float64 is enabled for the GP equivalence tests (the
paper's LAPACK pipeline is float64); model/kernel tests pass explicit dtypes.

NOTE: XLA_FLAGS device-count forcing is deliberately NOT set here — smoke
tests must see the real single CPU device. Multi-device shard_map coverage
runs in subprocesses (tests/test_shardmap.py) with their own XLA_FLAGS.
"""
import jax
import pytest

jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
