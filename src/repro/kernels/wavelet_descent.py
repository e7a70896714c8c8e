"""Pallas kernel family: fused 3-level WTBC count descent (DESIGN.md §6, §9).

``count_range(w, lo, hi)`` — the inner operation of Algorithm 1 — performs two
``rank_b`` per wavelet-tree level.  Launched through ``byte_rank`` that is six
kernel launches per (word, range) triple, and the level-L positions depend on
the level-(L-1) rank results, so the launches cannot even overlap.  The
kernels here fuse the whole root-to-leaf descent for a *batch* of M triples
into a single launch, and each triple's three levels run back-to-back.

Because the level-1/2 tile indices are data-dependent (they come from the
level-0/1 ranks computed *inside* the kernel), the usual scalar-prefetch
BlockSpec gather cannot feed them.  The two lowerings differ only in how the
in-kernel gather is expressed; the descent itself — range mapping, clipping,
leaf selection — is ONE shared definition (``_descent_levels``), so the TPU,
GPU and interpret paths cannot drift apart:

* **TPU** (``_kernel_tpu``): one grid step per chunk of triples, whose
  scalars (codeword bytes, node offsets, base ranks, ranges) come in as a
  flat int32 SMEM block.  Level byte arrays and counter matrices stay in
  ``ANY`` memory space and each rank issues manual ``pltpu.make_async_copy``
  DMAs into VMEM scratch of one whole ``(block//128, 128)`` byte block and
  of the aligned 8-row counter group holding its counter row — every DMA
  and slice aligned to the chip's tiling.  The endpoint DMAs of a level
  start together and overlap.
* **GPU / Triton** (``_kernel_gpu``): the same gathers are in-kernel
  ``plgpu.load`` calls — a (2, block) integer-indexed gather of the endpoint
  tiles and two scalar counter loads — which Pallas lowers to Triton masked
  gather loads from global memory.  This is also the body the interpreter
  runs, so CPU-only CI exercises the Triton code path bit-for-bit.

Per triple: 3 levels x 2 endpoints x (tile gather + counter gather +
masked compare-reduce).  The per-word node offsets / base ranks keep it at 2
ranks per level exactly like the scalar path in ``wtbc.count_range``.

Lowering selection (``kernels/backend.py``): compiled on real backends,
interpret only when explicitly requested or when no accelerator exists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas import triton as plgpu

from repro.core.bytemap import ByteMap
from repro.kernels import backend

MAX_LEVELS = 3
COUNTER_ROW = 256


def _tile_rank(tile, byte, pos, blk, *, block: int):
    """In-tile rank contribution: occurrences of ``byte`` in the ``blk``-th
    (block,) tile strictly before position ``pos``.  ``tile`` is (R, block)
    uint8; ``byte`` / ``pos`` / ``blk`` are (R,) int32.  Shared by every
    lowering — the single definition of the masked compare-reduce."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    hit = (tile == byte[:, None].astype(jnp.uint8)) \
        & (lane < (pos - blk * block)[:, None])
    return jnp.sum(hit.astype(jnp.int32), axis=1)


def _descent_levels(level_rank, cwb, off, base, cwl, a0, b0, lens):
    """The shared root-to-leaf descent: map the endpoint pair through the
    three levels, subtract the per-word base ranks, select the leaf's rank
    difference.  ``level_rank(L, byte, pa, pb) -> (ra, rb)`` supplies the
    lowering-specific gathered ranks (un-based); everything else — clipping,
    node offsets, leaf selection — is defined once here for TPU, GPU and
    interpret alike."""
    a, b = a0, b0
    res = jnp.int32(0)
    for L in range(MAX_LEVELS):
        byte = cwb[L]
        length = lens[L]
        pa = jnp.clip(off[L] + a, 0, length)
        pb = jnp.clip(off[L] + b, 0, length)
        # clamping the tile index into range makes the residual cutoff span
        # the whole final tile, which is exactly rank(length) (counter row
        # blk + one full-tile count) — no special casing for pos == length
        ra, rb = level_rank(L, byte, pa, pb)
        ra = ra - base[L]
        rb = rb - base[L]
        is_leaf = cwl == (L + 1)
        res = jnp.where(is_leaf, rb - ra, res)
        a, b = ra, rb
    return res


# ---------------------------------------------------------------------------
# TPU lowering: manual DMA tile gathers (ANY -> VMEM scratch)
# ---------------------------------------------------------------------------

# per-triple scalar fields, packed triple-major into one flat int32 SMEM
# stream: codeword bytes, node offsets, base ranks (one per level), codeword
# length, range endpoints
_F_CWB, _F_OFF, _F_BASE = 0, MAX_LEVELS, 2 * MAX_LEVELS
_F_CWL, _F_LO, _F_HI = 3 * MAX_LEVELS, 3 * MAX_LEVELS + 1, 3 * MAX_LEVELS + 2
N_FIELDS = 3 * MAX_LEVELS + 3
LANES = 128
TPU_CHUNK = 1024     # triples per grid step (1-D SMEM blocks tile by 1024)


def _kernel_tpu(lens_ref, fields_ref, d0, c0, d1, c1, d2, c2,
                out_ref, tile, row, tsem, rsem, *, block: int,
                n_blocks: tuple[int, ...], chunk: int, n_triples: int):
    """One grid step = ``chunk`` triples, descended one after another (the
    last step stops at ``n_triples``).

    Every memory access is shaped for the (8,128)/(32,128) tiling: the level
    bytes are viewed as ``(n_blocks, block//128, 128)`` and a rank DMAs one
    whole ``(block//128, 128)`` block; a counter rank DMAs the aligned group
    of 8 counter rows holding its row and takes its entry with a masked
    reduce (no lane index computed in the kernel); per-triple tables are
    SMEM scalars."""
    data_refs = (d0, d1, d2)
    count_refs = (c0, c1, c2)
    rows = block // LANES
    pos_in_blk = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
                  + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    crow = jax.lax.broadcasted_iota(jnp.int32, (8, COUNTER_ROW), 0)
    clane = jax.lax.broadcasted_iota(jnp.int32, (8, COUNTER_ROW), 1)

    def level_rank(L, byte, pa, pb):
        blks = (jnp.minimum(pa // block, n_blocks[L] - 1),
                jnp.minimum(pb // block, n_blocks[L] - 1))
        copies = []
        for e, blk in enumerate(blks):
            grp = pl.multiple_of((blk // 8) * 8, 8)
            copies += [
                pltpu.make_async_copy(data_refs[L].at[blk], tile.at[e],
                                      tsem.at[e]),
                pltpu.make_async_copy(count_refs[L].at[pl.ds(grp, 8)],
                                      row.at[e], rsem.at[e]),
            ]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        out = []
        for e, (blk, pos) in enumerate(zip(blks, (pa, pb))):
            cnt = jnp.sum(jnp.where((crow == blk % 8) & (clane == byte),
                                    row[e], 0))
            hit = ((tile[e].astype(jnp.int32) == byte)
                   & (pos_in_blk < pos - blk * block))
            out.append(cnt + jnp.sum(hit.astype(jnp.int32)))
        return out[0], out[1]

    lens = [lens_ref[L] for L in range(MAX_LEVELS)]

    def one(j, carry):
        f = j * N_FIELDS
        field = lambda k: fields_ref[f + k]
        out_ref[j] = _descent_levels(
            level_rank,
            [field(_F_CWB + L) for L in range(MAX_LEVELS)],
            [field(_F_OFF + L) for L in range(MAX_LEVELS)],
            [field(_F_BASE + L) for L in range(MAX_LEVELS)],
            field(_F_CWL), field(_F_LO), field(_F_HI), lens)
        return carry

    n_here = jnp.minimum(chunk, n_triples - pl.program_id(0) * chunk)
    jax.lax.fori_loop(0, n_here, one, 0)


# ---------------------------------------------------------------------------
# GPU (Triton) lowering: in-kernel plgpu.load gathers from global memory
# ---------------------------------------------------------------------------

def _kernel_gpu(cwb_ref, off_ref, base_ref, cwlen_ref, lo_ref, hi_ref, len_ref,
                d0, c0, d1, c1, d2, c2,
                out_ref, *, block: int, n_blocks: tuple[int, ...]):
    i = pl.program_id(0)
    data_refs = (d0, d1, d2)
    count_refs = (c0, c1, c2)
    lane = jax.lax.broadcasted_iota(jnp.int32, (2, block), 1)

    def level_rank(L, byte, pa, pb):
        blk = jnp.stack([jnp.minimum(pa // block, n_blocks[L] - 1),
                         jnp.minimum(pb // block, n_blocks[L] - 1)])
        # endpoint tiles: one (2, block) integer-indexed gather — Triton
        # lowers this to masked gather loads from the flat byte stream
        tile = plgpu.load(data_refs[L].at[blk[:, None] * block + lane])
        # counter entries: the (blk, byte) cells of the flattened (blocks+1,
        # 256) counter matrix — two scalar loads, not a 256-wide row DMA
        cnt = plgpu.load(count_refs[L].at[blk * COUNTER_ROW + byte])
        intile = _tile_rank(tile, jnp.stack([byte, byte]),
                            jnp.stack([pa, pb]), blk, block=block)
        return cnt[0] + intile[0], cnt[1] + intile[1]

    out_ref[0] = _descent_levels(
        level_rank, cwb_ref[i], off_ref[i], base_ref[i], cwlen_ref[i],
        lo_ref[i], hi_ref[i], len_ref)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _level_arrays(levels: tuple[ByteMap, ...], block: int):
    """Per-level (tiles, counters, n_blocks) with empty levels padded to one
    zero tile so in-kernel gathers stay in bounds on every lowering (an empty
    level is never the selected leaf of a real word; its clipped positions
    are 0, so the padded reads contribute base-cancelled zeros)."""
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


def _descend_tpu(tiles, counters, n_blocks, lens, cwb, offs, bases, cwl,
                 los, his, *, block: int, interpret: bool):
    if block % LANES:
        raise ValueError(f"the TPU descent needs block % {LANES} == 0, got "
                         f"block={block}")
    M = cwb.shape[0]
    chunk = min(M, TPU_CHUNK)
    n_chunks = -(-M // chunk)
    pad = n_chunks * chunk - M
    fields = jnp.concatenate(
        [cwb, offs, bases, cwl[:, None], los[:, None], his[:, None]],
        axis=1).astype(jnp.int32)                      # (M, N_FIELDS)
    fields = jnp.pad(fields, ((0, pad), (0, 0))).reshape(-1)
    # (n_blocks, block) bytes as whole (block//128, 128) tiles; counter
    # matrices padded to a multiple of 8 rows so every aligned 8-row group
    # exists
    tiles = [t.reshape(t.shape[0], block // LANES, LANES) for t in tiles]
    counters = [jnp.pad(c, ((0, -c.shape[0] % 8), (0, 0))) for c in counters]
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    fn = pl.pallas_call(
        functools.partial(_kernel_tpu, block=block, n_blocks=n_blocks,
                          chunk=chunk, n_triples=M),
        grid=(n_chunks,),
        in_specs=[smem((MAX_LEVELS,), lambda c: (0,)),
                  smem((chunk * N_FIELDS,), lambda c: (c,))]
                 + [pl.BlockSpec(memory_space=pl.ANY)] * 6,
        out_specs=smem((chunk,), lambda c: (c,)),
        out_shape=jax.ShapeDtypeStruct((n_chunks * chunk,), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((2, block // LANES, LANES), jnp.uint8),  # byte tiles
            pltpu.VMEM((2, 8, COUNTER_ROW), jnp.int32),        # counter rows
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )
    out = fn(lens.astype(jnp.int32), fields, tiles[0], counters[0],
             tiles[1], counters[1], tiles[2], counters[2])
    return out[:M]


@functools.partial(jax.jit, static_argnames=("block", "kind", "interpret"))
def _descend(levels, cw, cw_len, node_off, base_rank, words, los, his, *,
             block: int, kind: str, interpret: bool) -> jnp.ndarray:
    M = words.shape[0]
    words = words.astype(jnp.int32)
    cwb = cw[words].astype(jnp.int32)                  # (M, 3) codeword bytes
    offs = node_off[words]                             # (M, 3)
    bases = base_rank[words]                           # (M, 3)
    cwl = cw_len[words]                                # (M,)
    lens = jnp.stack([lv.length for lv in levels])     # (3,)
    tiles, counters, n_blocks = _level_arrays(levels, block)

    if kind == "tpu":
        return _descend_tpu(tiles, counters, n_blocks, lens, cwb, offs, bases,
                            cwl, los, his, block=block, interpret=interpret)

    # gpu / Triton: flat streams, everything gathered in-kernel
    flat = [t.reshape(-1) for t in tiles]
    cflat = [c.reshape(-1) for c in counters]
    params = {} if interpret else {
        "compiler_params": plgpu.CompilerParams(num_warps=4)}
    fn = pl.pallas_call(
        functools.partial(_kernel_gpu, block=block, n_blocks=n_blocks),
        grid=(M,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 13,
        out_specs=pl.BlockSpec((1,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((M,), jnp.int32),
        interpret=interpret,
        **params,
    )
    return fn(cwb, offs, bases, cwl,
              los.astype(jnp.int32), his.astype(jnp.int32), lens,
              flat[0], cflat[0], flat[1], cflat[1], flat[2], cflat[2])


def wavelet_descent(levels: tuple[ByteMap, ...], cw: jnp.ndarray,
                    cw_len: jnp.ndarray, node_off: jnp.ndarray,
                    base_rank: jnp.ndarray, words: jnp.ndarray,
                    los: jnp.ndarray, his: jnp.ndarray, *, block: int,
                    lowering: str | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Batched fused count: occurrences of word-rank ``words[i]`` in the root
    range ``[los[i], his[i])``.  Returns (M,) int32.

    ``levels`` are the WTBC's per-level ByteMaps (uniform ``block``); ``cw`` /
    ``cw_len`` / ``node_off`` / ``base_rank`` the index's per-word tables.

    ``lowering`` / ``interpret`` default to :func:`backend.kernel_plan` —
    compiled TPU or Triton kernel on a real accelerator, the portable Triton
    body under the interpreter otherwise.  Resolution happens here, outside
    the jit trace, so forced plans never leak into cached executables.
    """
    plan = backend.kernel_plan(lowering, interpret)
    return _batched_descend(block, plan.kind, plan.interpret)(
        levels, cw, cw_len, node_off, base_rank, words, los, his)


@functools.lru_cache(maxsize=None)
def _batched_descend(block: int, kind: str, interpret: bool):
    """``_descend`` with a batching rule: under ``vmap`` (the search cores
    vmap their per-row bodies) the batch of triple lists becomes ONE longer
    triple list — one launch, no batch grid axis for the kernel's blocks to
    be tiled over."""
    @jax.custom_batching.custom_vmap
    def descend(*args):
        return _descend(*args, block=block, kind=kind, interpret=interpret)

    @descend.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(jax.tree.leaves(in_batched[:5])):
            raise NotImplementedError("vmap over the index tables of a "
                                      "descent")
        triples = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                   for a, b in zip(args[5:], in_batched[5:])]
        shape = triples[0].shape
        out = descend(*args[:5], *(t.reshape(-1) for t in triples))
        return out.reshape(shape), True

    return descend
