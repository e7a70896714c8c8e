"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

Tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle; the oracles
are also the CPU fallback paths used when kernels are disabled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bytemap, wtbc
from repro.core.bitvec import WORDS_PER_BLOCK


def byte_rank_ref(data_padded: jnp.ndarray, counts: jnp.ndarray,
                  length: jnp.ndarray, bytes_q: jnp.ndarray,
                  pos_q: jnp.ndarray, *, block: int) -> jnp.ndarray:
    """vmap'd counter-gather + masked count (mirrors bytemap.rank)."""
    pos_q = jnp.clip(pos_q.astype(jnp.int32), 0, length)

    def one(b, p):
        blk = p // block
        base = counts[blk, b]
        chunk = jax.lax.dynamic_slice_in_dim(data_padded, blk * block, block)
        mask = jnp.arange(block, dtype=jnp.int32) < (p - blk * block)
        return base + jnp.sum((chunk == b.astype(jnp.uint8)) & mask, dtype=jnp.int32)

    return jax.vmap(one)(bytes_q, pos_q)


def bitmap_rank1_ref(words: jnp.ndarray, counts: jnp.ndarray,
                     n_bits: jnp.ndarray, pos_q: jnp.ndarray) -> jnp.ndarray:
    pos_q = jnp.clip(pos_q.astype(jnp.int32), 0, n_bits)

    def one(p):
        blk = p // (WORDS_PER_BLOCK * 32)
        chunk = jax.lax.dynamic_slice_in_dim(words, blk * WORDS_PER_BLOCK,
                                             WORDS_PER_BLOCK)
        n_valid = jnp.clip(p - blk * WORDS_PER_BLOCK * 32
                           - jnp.arange(WORDS_PER_BLOCK, dtype=jnp.int32) * 32, 0, 32)
        full = jnp.uint32(0xFFFFFFFF)
        mask = jnp.where(n_valid >= 32, full,
                         (jnp.uint32(1) << n_valid.astype(jnp.uint32)) - jnp.uint32(1))
        return counts[blk] + jnp.sum(
            jax.lax.population_count(chunk & mask).astype(jnp.int32))

    return jax.vmap(one)(pos_q)


def scored_topk_ref(cands: jnp.ndarray, query: jnp.ndarray, *, k: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    scores = cands.astype(jnp.float32) @ query.astype(jnp.float32)
    return jax.lax.top_k(scores, k)


def wavelet_count_ref(levels, cw, cw_len, node_off, base_rank,
                      words, los, his) -> jnp.ndarray:
    """Batched 3-level count descent, pure jnp (mirrors wtbc.count_range).

    Same math as the ``wavelet_descent`` kernel: per level the 2·M endpoint
    ranks run as one vectorized batch (the level-to-level dependency is the
    only sequential part).  Oracle for the kernel and the vmap-safe CPU path.
    """
    words = words.astype(jnp.int32)
    M = words.shape[0]
    a = los.astype(jnp.int32)
    b = his.astype(jnp.int32)
    res = jnp.zeros((M,), jnp.int32)
    for L, lv in enumerate(levels):
        byte = cw[words, L]
        off = node_off[words, L]
        base = base_rank[words, L]
        pos = jnp.concatenate([off + a, off + b])            # (2M,)
        r = jax.vmap(lambda bb, pp: bytemap.rank(lv, bb, pp))(
            jnp.tile(byte, 2), pos)
        ra, rb = r[:M] - base, r[M:] - base
        is_leaf = cw_len[words] == (L + 1)
        res = jnp.where(is_leaf, rb - ra, res)
        a, b = ra, rb
    return res


def wavelet_locate_ref(levels, cw, cw_len, node_off, base_rank, occ, n,
                       words, js) -> jnp.ndarray:
    """Batched locate, pure jnp: ``wtbc.locate_walk`` over ``bytemap.select``
    vmapped over the M pairs (every lane pays its selects, dead ones too).
    Oracle for the ``wavelet_locate`` kernel and its path off the TPU."""
    def one(w, j):
        return wtbc.locate_walk(
            lambda L, byte, k: bytemap.select(levels[L], byte, k),
            cw[w].astype(jnp.int32), node_off[w], base_rank[w], cw_len[w],
            j, occ[w], n)

    return jax.vmap(one)(words.astype(jnp.int32), js.astype(jnp.int32))
