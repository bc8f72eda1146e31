"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The TPU compiler is installed with libtpu and compiles for a chip that is
described, not attached, so these tests run on a CPU-only machine. They
catch what interpret mode cannot: block shapes that break the TPU tiling
rule (last two block dims divisible by 8 and 128, or equal to the array's)
and kernels that overrun VMEM. Shapes are the serving and fit widths:
``xcov_diag`` at |S| in {512, 1024} for every serving-ladder tile from 8 to
256, and the fit-time RBF covariance (|S| = 2,048 against a b = 1,600 block)
at the paper's input dimensions d = 5 (aimpeak) and d = 21 (sarcos), which
the wrapper pads to 128 lanes.

Only the worker given this file loads libtpu: the topology is described in a
fixture, never at import.
"""
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no libtpu
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from a persistent
    # cache without the chip, so keep it out of any cache; and compile as
    # the chip runs: 32-bit (conftest turns x64 on for the f64 suites, and
    # Mosaic refuses the 64-bit grid indices that would give the kernels)
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in saved:
        jax.config.update(k, False)
    yield SingleDeviceSharding(topo.devices[0])
    for k, v in saved.items():
        jax.config.update(k, v)


def _sds(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("block_q", [8, 64, 256])
@pytest.mark.parametrize("s_pad", [512, 1024])
def test_xcov_diag_compiles_for_v5e(one_chip, s_pad, block_q):
    from repro.kernels.rbf.xcov import xcov_diag_pallas
    n = 256                                     # the top serving bucket
    compiled = xcov_diag_pallas.lower(
        _sds(one_chip, n, 128), _sds(one_chip, s_pad, 128),
        _sds(one_chip, s_pad, s_pad), _sds(one_chip, s_pad, s_pad),
        _sds(one_chip, 1, s_pad), _sds(one_chip),
        s_valid=s_pad - 24, with_a2=True, block_q=block_q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d", [5, 21])
def test_rbf_covariance_compiles_for_v5e(one_chip, d):
    from repro.kernels.rbf.ops import rbf_covariance
    compiled = rbf_covariance.lower(
        _sds(one_chip, 2048, d), _sds(one_chip, 1600, d), _sds(one_chip),
        impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
