"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the TPU compiler is handed a described v5e topology, which
refuses what interpret mode never checks (block shapes off the (8, 128)
tiling, fast memory over budget).  The topology is described inside a
fixture, never at import, because only one process at a time may load
the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import pack_flush, quant_pack


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pack_rows_compiles(one_chip):
    _compile(lambda src, idx: pack_flush.pack_rows(src, idx,
                                                   interpret=False),
             jax.ShapeDtypeStruct((4096, 128), jnp.uint32,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one_chip))


def test_scatter_rows_compiles(one_chip):
    _compile(lambda dst, packed, idx: pack_flush.scatter_rows(
                 dst, packed, idx, interpret=False),
             jax.ShapeDtypeStruct((4096, 128), jnp.uint32,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((512, 128), jnp.uint32, sharding=one_chip),
             jax.ShapeDtypeStruct((512,), jnp.int32, sharding=one_chip))


def test_quantize_blockwise_compiles(one_chip):
    _compile(lambda x: quant_pack.quantize_blockwise(x, interpret=False),
             jax.ShapeDtypeStruct((1024, 4096), jnp.float32,
                                  sharding=one_chip))


def test_dequantize_blockwise_compiles(one_chip):
    _compile(lambda q, s: quant_pack.dequantize_blockwise(q, s,
                                                          interpret=False),
             jax.ShapeDtypeStruct((1024, 4096), jnp.int8, sharding=one_chip),
             jax.ShapeDtypeStruct((1024, 16), jnp.float32,
                                  sharding=one_chip))
