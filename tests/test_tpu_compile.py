"""Compile the search path's descent kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is described
and not attached, and refuses what the chip would refuse (unaligned slices,
unsupported memory accesses) — errors the Pallas interpreter cannot show.
Shapes are the chip smoke's collection (``chip_smoke.py``: 27,000 documents,
~16.8M tokens, vocabulary 200,000) with M=512 (word, range) triples for the
descent and M=65,536 (word, j) pairs for the locate (16 queries x 4 words x
a df_cap of 1,024, DRB/OR's batch).

All chip-compile tests live in this one file: the topology is described in a
module fixture, so only the worker that runs this file loads the TPU
library.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bytemap import ByteMap
from repro.kernels import ref
from repro.kernels import wavelet_descent as wd
from repro.kernels import wavelet_locate as wl

LEVEL_SIZES = (16_781_074, 4_234_069, 1_069_914)   # smoke collection levels
VOCAB = 200_000
M = 512
LOCATE_M = 65_536


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


def _spec(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


def _index_args(sharding, block):
    """(levels, cw, cw_len, node_off, base_rank) of the smoke collection."""
    spec = _spec(sharding)
    levels = []
    for n in LEVEL_SIZES:
        nb = -(-n // block)
        levels.append(ByteMap(data=spec((nb * block,), jnp.uint8),
                              counts=spec((nb + 1, 256), jnp.int32),
                              length=spec((), jnp.int32), block=block))
    return (tuple(levels), spec((VOCAB, 3), jnp.uint8),
            spec((VOCAB,), jnp.int32), spec((VOCAB, 3), jnp.int32),
            spec((VOCAB, 3), jnp.int32))


def _descent_args(sharding, block, batch=()):
    triple = _spec(sharding)(batch + (M,), jnp.int32)
    return _index_args(sharding, block) + (triple, triple, triple)


def _locate_args(sharding, block, batch=()):
    spec = _spec(sharding)
    pair = spec(batch + (LOCATE_M // math.prod(batch),), jnp.int32)
    return _index_args(sharding, block) + (
        spec((VOCAB,), jnp.int32), spec((), jnp.int32), pair, pair)


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


@pytest.mark.parametrize("block", [32768, 4096])
def test_tpu_locate_compiles(one_chip, no_compile_cache, block):
    """At block 32768 every level's counters sit in VMEM; at 4096 those of
    levels 0 and 1 (4,098 and 1,035 rows) exceed the budget and are DMA'd
    per probe."""
    args = _locate_args(one_chip, block)
    resident = [wl._resident(lv.counts.shape[0] + -lv.counts.shape[0] % 8)
                for lv in args[0]]
    assert resident == ([True] * 3 if block == 32768 else [False, False, True])
    compiled = wl._locate.lower(*args, block=block,
                                interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pairs' scalars go down as (chunk,)-minor blocks: no lane-padded
    # (M, fields) temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


def test_tpu_locate_compiles_under_vmap(one_chip, no_compile_cache):
    """DRB/OR's executor vmaps its rows: the batching rule flattens the
    (16, 4,096) pairs into one launch of 65,536."""
    block = 32768
    fn = wl.batched_locate(block, False)
    args = _locate_args(one_chip, block, batch=(16,))
    compiled = jax.jit(jax.vmap(fn, in_axes=(None,) * 7 + (0, 0))
                       ).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
