"""The main path's Pallas kernels, and the matmul payload's precision,
compiled for a described TPU v5e chip.

Nothing runs: the installed TPU compiler compiles each kernel at a real
size for a chip that is described, not attached, and refuses what the
chip would refuse (VMEM overflow, misaligned blocks).  The interpret-mode
tests in ``test_kernels.py`` cannot see those faults.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [8192, 16384])
def test_jacobi_sweep_compiles_at_real_width(one_chip, n):
    from repro.kernels.stencil import jacobi_sweep

    hlo = _compiled_hlo(
        lambda x: jacobi_sweep(x, interpret=False), one_chip,
        ((n, n), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shape", [(1024, 1024), (1023, 1024), (1, 1024)])
def test_stencil5_block_compiles_for_runtime_blocks(one_chip, shape):
    """A full runtime block and the ragged fragments a shifted view of it
    produces (not multiples of the (8, 128) tile)."""
    from repro.kernels.stencil import stencil5_block

    hlo = _compiled_hlo(
        lambda *xs: stencil5_block(*xs, weight=0.2, interpret=False),
        one_chip, *[(shape, jnp.float32)] * 5,
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k", [1, 1024])
def test_float32_matmul_payload_takes_no_bfloat16_pass(one_chip, k):
    """The matmul payload's program for a float32 product (``HIGHEST``)
    on runtime blocks: a product over an inner dimension of 1 (the
    N-body outer products) compiles to an f32 broadcast-multiply, one
    over 1024 to a convolution at the highest operand precision."""
    from repro.exec.backend import _repro_matmul

    hlo = _compiled_hlo(
        _repro_matmul(jnp, None, None, False, True, jax.lax.Precision.HIGHEST),
        one_chip, *[((1024, k), jnp.float32)] * 2,
    )
    if k == 1:
        assert "convolution" not in hlo and "multiply" in hlo
    else:
        assert "operand_precision={highest,highest}" in hlo


def test_flash_attention_compiles_gqa(one_chip):
    from repro.kernels.flash_attention import flash_attention

    B, S, H, KV, d = 1, 4096, 16, 4, 128
    hlo = _compiled_hlo(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        one_chip,
        ((B, S, H, d), jnp.bfloat16),
        ((B, S, KV, d), jnp.bfloat16),
        ((B, S, KV, d), jnp.bfloat16),
    )
    assert "tpu_custom_call" in hlo
