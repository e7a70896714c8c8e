"""Pallas kernel: one whole device-resident beam iteration (DESIGN.md §9).

``core/mega.py`` runs Algorithm 1 with per-row pool frontiers; its while-loop
body is a host-orchestrated chain — lex-argmax extraction, a
``count_range_batch`` launch, scoring, two pool inserts — each a separate XLA
op over the full (B, cap) state.  This kernel fuses the ENTIRE trip into a
single launch with one grid step per batch row:

  pop      in-kernel lex-argmax over the row's (cap,) pool vectors (the same
           three masked reductions as ``heap.lex_argmax``), slot cleared in
           registers;
  emit     the popped singleton written straight to the row's output slot;
  descend  the Q-word × 3-level WTBC count of the left child, sharing
           ``wavelet_descent._descent_levels`` — the one descent definition —
           with Q-wide ``plgpu.load`` tile/counter gathers;
  score    an in-kernel (Q,)·(Q,) dot, unrolled round-each-product /
           add-left-to-right — the reduction ``einsum('bq,bq->b')`` compiles
           to (a fused ``jnp.dot`` FMA-contracts and drifts 1 ulp);
  push     two first-free-slot inserts, scalar scatters into the pool.

The frontier never round-trips: state arrays are input/output aliased, and a
trip writes only the touched cells (popped slot, ≤2 insert slots, the
emission slot, five per-row scalars) instead of materializing new (B, cap)
pools.  Gathers are Triton-style ``plgpu.load`` with computed flat indices, so
the lowering is GPU (or the Pallas interpreter — how CPU CI runs it); the TPU
path keeps the jnp mega body around the DMA-gather descent kernel.

Bitwise contract (pinned by tests/test_beam_fused.py): at matched
(B, Q, cap, k) this body is bit-for-bit ``mega.topk_dr_mega``'s — same pops,
same emissions, same overflow latching, including undersized-cap overflow
edges (cap stays EXACT; reductions run over pow2 lanes with padding masked,
never by growing cap).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import triton as plgpu

from repro.core import heap as H
from repro.kernels import backend
from repro.kernels.wavelet_descent import (COUNTER_ROW, _descent_levels,
                                           _level_arrays, _tile_rank)

NEG_INF = -float("inf")
INT32_MAX = 2**31 - 1
INT32_MIN = -(2**31)


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _at(vec, idx):
    """vec[idx] for a register vector and a traced scalar index (gather-free:
    a masked sum, exact because all other lanes contribute the identity)."""
    lane = jax.lax.iota(jnp.int32, vec.shape[0])
    zero = jnp.zeros((), vec.dtype)
    return jnp.sum(jnp.where(lane == idx, vec, zero))


def _kernel(words_ref, wmask_ref, idfw_ref,
            ps_in, p0_in, p1_in, ptf_in, od_in, os_in,
            no_in, it_in, pp_in, ov_in,
            sep_ref, nn_ref, len_ref, cwb_ref, cwl_ref, noff_ref, brank_ref,
            dA, cA, dB, cB, dC, cC,
            ps_out, p0_out, p1_out, ptf_out, od_out, os_out,
            no_out, it_out, pp_out, ov_out,
            *, Q: int, cap: int, k: int, conjunctive: bool,
            max_pops: int | None, block: int, n_blocks: tuple[int, ...]):
    i = pl.program_id(0)
    cap2 = _pow2(cap)
    lane = jax.lax.iota(jnp.int32, cap2)
    cmask = lane < cap
    qlane = jax.lax.iota(jnp.int32, Q)

    # ---- row state into registers (clamped loads + mask: always in-bounds).
    # ALL mutable-state reads go through the *_out refs: they alias the
    # inputs (pre-initialized), and — unlike the _in refs, which keep the
    # input snapshot in interpret mode — they observe this step's stores,
    # so read-after-write inside one trip is coherent.
    del ps_in, p0_in, p1_in, ptf_in, od_in, os_in, no_in, it_in, pp_in, ov_in
    cidx = i * cap + jnp.minimum(lane, cap - 1)
    s = jnp.where(cmask, plgpu.load(ps_out.at[cidx]), jnp.float32(NEG_INF))
    d0v = plgpu.load(p0_out.at[cidx])
    d1v = plgpu.load(p1_out.at[cidx])
    n_out = plgpu.load(no_out.at[i])
    iters = plgpu.load(it_out.at[i])
    pops = plgpu.load(pp_out.at[i])
    ov = plgpu.load(ov_out.at[i])

    active = (n_out < k) & jnp.any(s > NEG_INF)
    if max_pops is not None:
        active = active & (pops < max_pops)

    # ---- pop: heap.lex_argmax verbatim over the register pool
    valid = s > NEG_INF
    c = valid & (s == jnp.max(s))
    d0_ = jnp.where(c, d0v, INT32_MAX)
    c = c & (d0_ == jnp.min(d0_))
    j = jnp.argmax(jnp.where(c, d1v, INT32_MIN)).astype(jnp.int32)
    s_p = _at(s, j)
    d0 = _at(d0v, j)
    d1 = _at(d1v, j)
    tf = plgpu.load(ptf_out.at[i * cap * Q + j * Q + qlane])
    s = jnp.where((lane == j) & active, jnp.float32(NEG_INF), s)
    plgpu.store(ps_out.at[i * cap + j], _at(s, j))

    # ---- emit a popped singleton (slot k is the trash lane)
    single = active & ((d1 - d0) == 1)
    multi = active & ~single
    slot = jnp.where(single & (n_out < k), n_out, k)
    oidx = i * (k + 1) + slot
    plgpu.store(od_out.at[oidx], jnp.where(single, d0, plgpu.load(od_out.at[oidx])))
    plgpu.store(os_out.at[oidx], jnp.where(single, s_p, plgpu.load(os_out.at[oidx])))
    n_out = jnp.minimum(n_out + single.astype(jnp.int32), k)

    # ---- split: segment extents from sep_pos, then the fused Q-word descent
    n = nn_ref[0]
    n_docs = nn_ref[1]

    def doc_start(d):
        prev = plgpu.load(sep_ref.at[jnp.maximum(d - 1, 0)])
        return jnp.where(d == 0, jnp.int32(0), prev + 1)

    mid = (d0 + d1) // 2
    lo1 = doc_start(d0)
    hi1 = jnp.where(mid >= n_docs, n, doc_start(mid))

    wq = plgpu.load(words_ref.at[i * Q + qlane])
    mq = plgpu.load(wmask_ref.at[i * Q + qlane])
    idfw = plgpu.load(idfw_ref.at[i * Q + qlane])
    cwb = [plgpu.load(cwb_ref.at[wq * 3 + L]) for L in range(3)]
    offq = [plgpu.load(noff_ref.at[wq * 3 + L]) for L in range(3)]
    baseq = [plgpu.load(brank_ref.at[wq * 3 + L]) for L in range(3)]
    cwl = plgpu.load(cwl_ref.at[wq])
    lens = [len_ref[L] for L in range(3)]
    data_refs = (dA, dB, dC)
    count_refs = (cA, cB, cC)
    blane = jax.lax.broadcasted_iota(jnp.int32, (Q, block), 1)

    def level_rank(L, byte, pa, pb):
        def rank1(p):
            blk = jnp.minimum(p // block, n_blocks[L] - 1)
            tile = plgpu.load(data_refs[L].at[blk[:, None] * block + blane])
            cnt = plgpu.load(count_refs[L].at[blk * COUNTER_ROW + byte])
            return cnt + _tile_rank(tile, byte, p, blk, block=block)
        return rank1(pa), rank1(pb)

    tf1 = _descent_levels(level_rank, cwb, offq, baseq, cwl,
                          jnp.full((Q,), 0, jnp.int32) + lo1,
                          jnp.full((Q,), 0, jnp.int32) + hi1, lens) * mq
    tf2 = tf - tf1

    # score: strict round-each-product, add-left-to-right — what the jnp
    # body's einsum('bq,bq->b') compiles to.  A plain jnp.dot here gets
    # FMA-contracted (extra-precision products), which drifts 1 ulp off the
    # einsum on some inputs and would break the bitwise contract; the lane
    # extraction is a masked sum (exact: other lanes add the identity).
    def row_dot(tfv):
        prod = tfv.astype(jnp.float32) * idfw
        acc = jnp.float32(0.0)
        for q in range(Q):
            acc = acc + jnp.sum(jnp.where(qlane == q, prod, jnp.float32(0.0)))
        return acc

    s1 = row_dot(tf1)
    s2 = row_dot(tf2)

    def seg_valid(tfv, sc):
        if conjunctive:
            return jnp.all((tfv > 0) | (mq == 0)) & jnp.any(mq != 0)
        return sc > 0.0

    # ---- push: two first-free-slot inserts (scalar scatters)
    def insert(s, d0v, d1v, ov, sc, da, db, tfv, enable):
        free = (s == NEG_INF) & cmask
        has_free = jnp.any(free)
        slot = jnp.argmax(free).astype(jnp.int32)
        ok = enable & has_free
        ov = ov | (enable & ~has_free).astype(jnp.int32)
        pidx = i * cap + slot
        plgpu.store(ps_out.at[pidx], jnp.where(ok, sc, _at(s, slot)))
        plgpu.store(p0_out.at[pidx], jnp.where(ok, da, _at(d0v, slot)))
        plgpu.store(p1_out.at[pidx], jnp.where(ok, db, _at(d1v, slot)))
        tidx = i * cap * Q + slot * Q + qlane
        plgpu.store(ptf_out.at[tidx], jnp.where(ok, tfv, plgpu.load(ptf_out.at[tidx])))
        s = jnp.where((lane == slot) & ok, sc, s)
        d0v = jnp.where((lane == slot) & ok, da, d0v)
        d1v = jnp.where((lane == slot) & ok, db, d1v)
        return s, d0v, d1v, ov

    s, d0v, d1v, ov = insert(s, d0v, d1v, ov, s1, d0, mid, tf1,
                             multi & seg_valid(tf1, s1))
    s, d0v, d1v, ov = insert(s, d0v, d1v, ov, s2, mid, d1, tf2,
                             multi & seg_valid(tf2, s2))

    plgpu.store(no_out.at[i], n_out)
    plgpu.store(it_out.at[i], iters + active.astype(jnp.int32))
    plgpu.store(pp_out.at[i], pops + active.astype(jnp.int32))
    plgpu.store(ov_out.at[i], ov)


def fused_beam_step(idx, words, wmask, idf_w, pool, out_docs, out_scores,
                    n_out, iters, pops, overflowed, *, k: int,
                    conjunctive: bool, cap: int, max_pops: int | None,
                    interpret: bool):
    """Run ONE mega trip for every row in a single launch; returns the same
    state tuple shapes ``mega.topk_dr_mega``'s jnp body produces.  Call from
    inside the (jitted) mega while-loop — ``interpret`` must be resolved
    outside the trace (``backend.descent_plan``)."""
    B, Q = words.shape
    assert Q & (Q - 1) == 0, "fused beam step requires a pow2 Q bucket"
    block = idx.levels[0].block
    assert block & (block - 1) == 0, "fused beam step requires pow2 block"
    pool_s, pool_d0, pool_d1, pool_tf = pool
    tiles, counters, n_blocks = _level_arrays(idx.levels, block)
    flat = [t.reshape(-1) for t in tiles]
    cflat = [c.reshape(-1) for c in counters]
    nn = jnp.stack([jnp.int32(idx.n), jnp.int32(idx.n_docs)])
    lens = jnp.stack([jnp.int32(lv.length) for lv in idx.levels])
    sep = idx.sep_pos.astype(jnp.int32)
    if sep.shape[0] == 0:
        sep = jnp.zeros((1,), jnp.int32)

    state_in = (pool_s.reshape(-1), pool_d0.reshape(-1), pool_d1.reshape(-1),
                pool_tf.reshape(-1), out_docs.reshape(-1),
                out_scores.reshape(-1), n_out, iters, pops,
                overflowed.astype(jnp.int32))
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state_in]
    fn = pl.pallas_call(
        functools.partial(_kernel, Q=Q, cap=cap, k=k, conjunctive=conjunctive,
                          max_pops=max_pops, block=block, n_blocks=n_blocks),
        grid=(B,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 26,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 10,
        out_shape=out_shape,
        input_output_aliases={3 + t: t for t in range(10)},
        interpret=interpret,
    )
    (ps, p0, p1, ptf, od, os_, no, it, pp, ov) = fn(
        words.reshape(-1).astype(jnp.int32),
        wmask.reshape(-1).astype(jnp.int32),
        idf_w.reshape(-1).astype(jnp.float32),
        *state_in,
        sep, nn, lens,
        idx.cw.astype(jnp.int32).reshape(-1),
        idx.cw_len.astype(jnp.int32),
        idx.node_off.astype(jnp.int32).reshape(-1),
        idx.base_rank.astype(jnp.int32).reshape(-1),
        flat[0], cflat[0], flat[1], cflat[1], flat[2], cflat[2])
    cap_ = pool_s.shape[1]
    return ((ps.reshape(B, cap_), p0.reshape(B, cap_), p1.reshape(B, cap_),
             ptf.reshape(B, cap_, Q)),
            od.reshape(B, k + 1), os_.reshape(B, k + 1),
            no, it, pp, ov.astype(bool))


__all__ = ["fused_beam_step"]
_ = (H, backend)  # parity anchors: the kernel mirrors heap.lex_argmax
