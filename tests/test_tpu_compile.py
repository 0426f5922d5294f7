"""Ahead-of-time v5e compiles of the ``ckpt_delta`` kernels on the
checkpoint trigger and restore path, at state-sized buffers.

Interpret mode accepts block shapes Mosaic refuses, so every kernel is
compiled here for a described (not attached) v5e chip and must come out
as a ``tpu_custom_call``.  The topology is described inside a fixture,
only after a test of this file has started: the TPU library may be loaded
by one process at a time, and it stays with the worker that runs this
file.  The persistent compilation cache is off around these compiles: a
TPU executable written to it here could not be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ckpt_delta import ops
from repro.kernels.ckpt_delta.ref import GROUP

# the packed f32 subtree of yi-6b at one layer and vocab 8000 with bf16
# Adam state (234,508 groups), and an odd group count that leaves a
# ragged final kernel block
GROUPS = (4096, 234_508, 4099)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _rows(sharding, ng: int, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((ng, GROUP), dtype, sharding=sharding)


def _groups(sharding, ng: int, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((ng,), dtype, sharding=sharding)


@pytest.mark.parametrize("ng", GROUPS)
def test_pack_flat_compiles_for_v5e(one_chip, ng):
    leaf = jax.ShapeDtypeStruct(((ng - 1) * GROUP + 7,), jnp.float32,
                                sharding=one_chip)
    small = jax.ShapeDtypeStruct((4096, 3), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a, b: ops.pack_flat([a, b])).lower(
        leaf, small).compile()
    assert compiled.memory_analysis().output_size_in_bytes \
        >= (ng + 12) * GROUP * 4


@pytest.mark.parametrize("ng", GROUPS)
def test_flat_lossless_encode_compiles_for_v5e(one_chip, ng):
    text = _compiled_text(
        lambda a, b, g: ops.flat_lossless_encode(a, b, g, num_leaves=3),
        _rows(one_chip, ng), _rows(one_chip, ng), _groups(one_chip, ng))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ng", GROUPS)
def test_flat_int8_encode_compiles_for_v5e(one_chip, ng):
    text = _compiled_text(
        lambda a, b, g: ops.flat_int8_encode(a, b, g, num_leaves=3),
        _rows(one_chip, ng), _rows(one_chip, ng), _groups(one_chip, ng))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ng", GROUPS)
def test_lossless_decode_compiles_for_v5e(one_chip, ng):
    text = _compiled_text(ops.lossless_decode, _rows(one_chip, ng),
                          _rows(one_chip, ng),
                          _rows(one_chip, ng, jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ng", GROUPS)
def test_delta_decode_compiles_for_v5e(one_chip, ng):
    text = _compiled_text(ops.delta_decode, _rows(one_chip, ng, jnp.int8),
                          _groups(one_chip, ng, jnp.float32))
    assert "tpu_custom_call" in text
