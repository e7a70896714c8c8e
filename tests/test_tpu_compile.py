"""Compile the search path's descent kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is described
and not attached, and refuses what the chip would refuse (unaligned slices,
unsupported memory accesses) — errors the Pallas interpreter cannot show.
Shapes are the chip smoke's collection (``chip_smoke.py``: 27,000 documents,
~16.8M tokens, vocabulary 200,000) with M=512 (word, range) triples.

All chip-compile tests live in this one file: the topology is described in a
module fixture, so only the worker that runs this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bytemap import ByteMap
from repro.kernels import ref
from repro.kernels import wavelet_descent as wd

LEVEL_SIZES = (16_781_074, 4_234_069, 1_069_914)   # smoke collection levels
VOCAB = 200_000
M = 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _descent_args(sharding, block, batch=()):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)
    levels = []
    for n in LEVEL_SIZES:
        nb = -(-n // block)
        levels.append(ByteMap(data=spec((nb * block,), jnp.uint8),
                              counts=spec((nb + 1, 256), jnp.int32),
                              length=spec((), jnp.int32), block=block))
    triple = spec(batch + (M,), jnp.int32)
    return (tuple(levels), spec((VOCAB, 3), jnp.uint8),
            spec((VOCAB,), jnp.int32), spec((VOCAB, 3), jnp.int32),
            spec((VOCAB, 3), jnp.int32), triple, triple, triple)


@pytest.mark.parametrize("block", [32768, 4096])
def test_tpu_descent_compiles(one_chip, no_compile_cache, block):
    compiled = wd._descend.lower(*_descent_args(one_chip, block), block=block,
                                 kind="tpu", interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tpu_descent_compiles_under_vmap(one_chip, no_compile_cache):
    """The search cores vmap their row bodies; the batching rule must turn
    the batch into one longer triple list the kernel can tile."""
    block = 32768
    fn = wd._batched_descend(block, "tpu", False)
    args = _descent_args(one_chip, block, batch=(4,))
    compiled = jax.jit(jax.vmap(fn, in_axes=(None,) * 5 + (0, 0, 0))
                       ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ref_descent_compiles(one_chip, no_compile_cache):
    compiled = jax.jit(ref.wavelet_count_ref).lower(
        *_descent_args(one_chip, 32768)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
