"""Pallas TPU kernel: batched WTBC locate (DESIGN.md §6, §9).

``wtbc.locate(w, j)`` walks leaf -> root with one ``select`` per level.
Under ``vmap`` (DRB/OR locates every (word, document) lane of a batch at
once) each jnp ``select`` becomes a gather of one whole counter block per
lane and level, dead lanes included.  This kernel runs the walk for a flat
batch of M ``(word, j)`` pairs in one launch and spends nothing on a dead
pair (j < 1 or j > occ[w]): it starts no DMA and returns ``n``.  The walk
itself — leaf start, base ranks, node offsets, the dead-pair rule — is
``wtbc.locate_walk``, shared with the scalar path and the oracle.

Layout, as in ``wavelet_descent``'s TPU lowering: one grid step per chunk of
up to ``CHUNK`` pairs, whose scalars (codeword bytes, node offsets, base
ranks, codeword length, j, occ) come in as a flat int32 SMEM block; the
level bytes stay in ``ANY`` memory viewed as ``(n_blocks, block//128,
128)``.  One select on level L:

* finds the counter block by binary search of the byte's counter column.
  A level whose counter matrix fits ``RESIDENT_COUNTER_BYTES`` keeps it
  resident in VMEM for the launch and each probe reads an aligned 8-row
  group there; a larger level DMAs that 8-row group per probe.  The choice
  follows the level's shape;
* DMAs exactly that one ``(block//128, 128)`` byte block into VMEM;
* finds the occurrence in the block with two small matmuls over the 0/1
  match matrix (hits at or before each lane of a row, hits in the rows
  before), exact in float32 since a block holds fewer than 2**24 bytes, and
  counts the positions whose running total is still short of the target.

The selects of one pair are serial (each level's target comes from the
level below); no DMA is overlapped with another pair's work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import wtbc
from repro.core.bytemap import ByteMap

MAX_LEVELS = wtbc.MAX_LEVELS
LANES = 128
COUNTER_ROW = 256
CHUNK = 1024                        # pairs per grid step
RESIDENT_COUNTER_BYTES = 1 << 20    # VMEM for one level's resident counters

# per-pair scalar fields: one flat int32 SMEM block per chunk, field-major
# (field k of pair i at k * chunk + i)
_F_CWB, _F_OFF, _F_BASE = 0, MAX_LEVELS, 2 * MAX_LEVELS
_F_CWL, _F_J, _F_OCC = 3 * MAX_LEVELS, 3 * MAX_LEVELS + 1, 3 * MAX_LEVELS + 2
N_FIELDS = 3 * MAX_LEVELS + 3


def _resident(counts_rows: int) -> bool:
    return counts_rows * COUNTER_ROW * 4 <= RESIDENT_COUNTER_BYTES


def _kernel(lens_ref, fields_ref, d0, c0, d1, c1, d2, c2, out_ref,
            tile, row, tsem, rsem, *, block: int, n_blocks: tuple[int, ...],
            resident: tuple[bool, ...], chunk: int, n_pairs: int):
    data_refs = (d0, d1, d2)
    count_refs = (c0, c1, c2)
    rows = block // LANES
    crow = jax.lax.broadcasted_iota(jnp.int32, (8, COUNTER_ROW), 0)
    clane = jax.lax.broadcasted_iota(jnp.int32, (8, COUNTER_ROW), 1)
    # 0/1 operators of the in-block prefix: lanes at or before a lane, rows
    # strictly before a row
    upto_lane = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
                 <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
                 ).astype(jnp.float32).astype(jnp.bfloat16)
    rows_before = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
                   < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
                   ).astype(jnp.float32).astype(jnp.bfloat16)
    lens = [lens_ref[L] for L in range(MAX_LEVELS)]

    def counter(L, b, byte):
        """counts[b, byte] of level L."""
        grp = pl.multiple_of((b // 8) * 8, 8)
        if resident[L]:
            group = count_refs[L][pl.ds(grp, 8), :]
        else:
            cp = pltpu.make_async_copy(count_refs[L].at[pl.ds(grp, 8)], row,
                                       rsem.at[0])
            cp.start()
            cp.wait()
            group = row[...]
        return jnp.sum(jnp.where((crow == b % 8) & (clane == byte), group, 0))

    def select(L, byte, k):
        # largest block b in [0, n_blocks) with counts[b, byte] < k, and
        # that count (counts[0, .] is 0, and k >= 1)
        def probe(st):
            lo, hi, base = st
            mid = (lo + hi + 1) // 2
            c = counter(L, mid, byte)
            right = c < k
            return (jnp.where(right, mid, lo), jnp.where(right, hi, mid - 1),
                    jnp.where(right, c, base))

        blk, _, base = jax.lax.while_loop(
            lambda st: st[0] < st[1], probe,
            (jnp.int32(0), jnp.int32(n_blocks[L] - 1), jnp.int32(0)))
        cp = pltpu.make_async_copy(data_refs[L].at[blk], tile, tsem.at[0])
        cp.start()
        cp.wait()
        hit = jnp.where(tile[...].astype(jnp.int32) == byte, 1.0, 0.0
                        ).astype(jnp.bfloat16)                  # (rows, 128)
        in_row = jnp.dot(hit, upto_lane, preferred_element_type=jnp.float32)
        row_tot = jnp.broadcast_to(in_row[:, LANES - 1:], (rows, LANES))
        before = jnp.dot(rows_before, row_tot.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        need = (k - base).astype(jnp.float32)
        at = jnp.sum((before + in_row < need).astype(jnp.int32))
        # past the last occurrence (k > the column's total) the scan runs
        # off the level's end, into its zero padding: select gives length
        return jnp.minimum(blk * block + at, lens[L])

    def level_select(L, byte, k):
        return jax.lax.cond(k >= 1, lambda: select(L, byte, k),
                            lambda: lens[L])

    def one(i, carry):
        field = lambda k: fields_ref[k * chunk + i]
        j, occ = field(_F_J), field(_F_OCC)
        n = lens_ref[MAX_LEVELS]

        def walk():
            return wtbc.locate_walk(
                level_select,
                [field(_F_CWB + L) for L in range(MAX_LEVELS)],
                [field(_F_OFF + L) for L in range(MAX_LEVELS)],
                [field(_F_BASE + L) for L in range(MAX_LEVELS)],
                field(_F_CWL), j, occ, n)

        out_ref[i] = jax.lax.cond((j >= 1) & (j <= occ), walk, lambda: n)
        return carry

    n_here = jnp.minimum(chunk, n_pairs - pl.program_id(0) * chunk)
    jax.lax.fori_loop(0, n_here, one, 0)


def _level_arrays(levels: tuple[ByteMap, ...], block: int):
    """Per-level (byte blocks, counters, n_blocks); an empty level becomes
    one zero block (no live pair reaches it: the walk passes k = 0 there)."""
    tiles, counters, n_blocks = [], [], []
    for lv in levels:
        nb = lv.counts.shape[0] - 1
        if nb <= 0:
            tiles.append(jnp.zeros((1, block), jnp.uint8))
            counters.append(jnp.zeros((2, COUNTER_ROW), jnp.int32))
            n_blocks.append(1)
        else:
            tiles.append(lv.data.reshape(nb, block))
            counters.append(lv.counts)
            n_blocks.append(nb)
    return tiles, counters, tuple(n_blocks)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _locate(levels, cw, cw_len, node_off, base_rank, occ, n, words, js, *,
            block: int, interpret: bool) -> jnp.ndarray:
    # the chip DMAs uint8 blocks in (4, 128) tiles; the interpreter takes
    # any whole number of 128-byte rows
    align = LANES if interpret else 4 * LANES
    if block % align:
        raise ValueError(f"the TPU locate needs block % {align} == 0, got "
                         f"block={block}")
    M = words.shape[0]
    words = words.astype(jnp.int32)
    chunk = min(M, CHUNK)
    n_chunks = -(-M // chunk)
    # field-major within each chunk — (n_chunks, N_FIELDS, chunk) — so no
    # (M, N_FIELDS) array is ever laid out with its short minor dimension
    cols = ([cw[words, L].astype(jnp.int32) for L in range(MAX_LEVELS)]
            + [node_off[words, L] for L in range(MAX_LEVELS)]
            + [base_rank[words, L] for L in range(MAX_LEVELS)]
            + [cw_len[words], js.astype(jnp.int32), occ[words]])
    fields = jnp.pad(jnp.stack(cols).astype(jnp.int32),
                     ((0, 0), (0, n_chunks * chunk - M)))
    fields = fields.reshape(N_FIELDS, n_chunks, chunk).transpose(1, 0, 2)
    fields = fields.reshape(-1)
    lens = jnp.stack([lv.length for lv in levels] + [n]).astype(jnp.int32)
    tiles, counters, n_blocks = _level_arrays(levels, block)
    tiles = [t.reshape(t.shape[0], block // LANES, LANES) for t in tiles]
    # counter matrices padded to a multiple of 8 rows so every aligned 8-row
    # group exists
    counters = [jnp.pad(c, ((0, -c.shape[0] % 8), (0, 0))) for c in counters]
    resident = tuple(_resident(c.shape[0]) for c in counters)
    count_specs = [
        pl.BlockSpec(c.shape, lambda i: (0, 0), pipeline_mode=pl.Buffered(1))
        if res else pl.BlockSpec(memory_space=pl.ANY)
        for c, res in zip(counters, resident)]
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    data_spec = pl.BlockSpec(memory_space=pl.ANY)
    fn = pl.pallas_call(
        functools.partial(_kernel, block=block, n_blocks=n_blocks,
                          resident=resident, chunk=chunk, n_pairs=M),
        grid=(n_chunks,),
        in_specs=[smem((MAX_LEVELS + 1,), lambda i: (0,)),
                  smem((chunk * N_FIELDS,), lambda i: (i,))]
                 + [s for L in range(MAX_LEVELS)
                    for s in (data_spec, count_specs[L])],
        out_specs=smem((chunk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_chunks * chunk,), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((block // LANES, LANES), jnp.uint8),   # byte block
            pltpu.VMEM((8, COUNTER_ROW), jnp.int32),          # counter group
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        interpret=interpret,
    )
    out = fn(lens, fields, tiles[0], counters[0], tiles[1], counters[1],
             tiles[2], counters[2])
    return out[:M]


@functools.lru_cache(maxsize=None)
def batched_locate(block: int, interpret: bool):
    """``_locate`` with a batching rule: under ``vmap`` (the executors vmap
    their per-row bodies) the batch of pair lists becomes ONE longer pair
    list — one launch for the whole batch."""
    @jax.custom_batching.custom_vmap
    def locate(*args):
        return _locate(*args, block=block, interpret=interpret)

    @locate.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(jax.tree.leaves(in_batched[:7])):
            raise NotImplementedError("vmap over the index tables of a "
                                      "locate")
        pairs = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                 for a, b in zip(args[7:], in_batched[7:])]
        shape = pairs[0].shape
        out = locate(*args[:7], *(p.reshape(-1) for p in pairs))
        return out.reshape(shape), True

    return locate
