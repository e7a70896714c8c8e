"""Wavelet Tree on Bytecodes (WTBC) — level-concatenated, array-native layout.

The paper's WTBC places the i-th bytes of each (s,c)-DC codeword in tree nodes:
the root holds every codeword's first byte in text order; the child ``B_b`` of
the root holds the second byte of every codeword starting with continuer ``b``;
and so on.  Decode walks down with ``rank``; locate walks up with ``select``;
count is a ``rank`` difference at the word's leaf node.

TPU-native adaptation (DESIGN.md §2): instead of pointer-linked nodes we store
**one contiguous byte array per level**; a node is the slice
``[offset, offset+len)`` given by a dense per-level offset table indexed by the
codeword's continuer prefix.  All traversals become integer arithmetic over
static arrays, so every query op below is jit/vmap-compatible.

Per-word acceleration (beyond-paper, free at build time): ``node_off[w, L]``
(absolute offset of the node word ``w`` traverses at level ``L``) and
``base_rank[w, L]`` (rank of ``w``'s level-L byte at that node's start) are
precomputed, halving the rank calls per count/locate.

The document separator '$' is word-rank 0 => its codeword is the single stopper
byte 0 and lives entirely in the root (the paper reserves the first codeword
for '$' for exactly this reason).  Separator positions are additionally kept in
a sorted array ``sep_pos`` — the paper's footnote-2 "faster structures for
those particular cases of select" — making document extents O(1) lookups.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bytemap, scdc
from repro.core.bytemap import ByteMap

MAX_LEVELS = scdc.MAX_CODE_LEN  # 3
SEP_RANK = 0                    # '$' is frequency-rank 0 by construction


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("levels", "offsets", "cw", "cw_len", "node_off", "base_rank",
                 "sep_pos", "df", "occ", "doc_len", "n", "n_docs"),
    meta_fields=("s", "c"))
@dataclasses.dataclass(frozen=True)
class WTBCIndex:
    """The full index: a pytree of device arrays + static (s,c) metadata."""

    levels: tuple[ByteMap, ...]        # MAX_LEVELS ByteMaps (possibly empty)
    offsets: tuple[jnp.ndarray, ...]   # per-level dense node offset tables
    cw: jnp.ndarray                    # (V, MAX_LEVELS) uint8 codeword bytes
    cw_len: jnp.ndarray                # (V,) int32
    node_off: jnp.ndarray              # (V, MAX_LEVELS) int32
    base_rank: jnp.ndarray             # (V, MAX_LEVELS) int32
    sep_pos: jnp.ndarray               # (n_docs,) int32 separator positions in root
    df: jnp.ndarray                    # (V,) int32 document frequency per word-rank
    occ: jnp.ndarray                   # (V,) int32 total occurrences per word-rank
    doc_len: jnp.ndarray               # (n_docs,) int32 tokens per doc (sans '$')
    n: jnp.ndarray                     # () int32 total tokens (incl. separators)
    n_docs: jnp.ndarray                # () int32
    s: int                             # static: stoppers
    c: int                             # static: continuers

    @property
    def vocab_size(self) -> int:
        return self.cw.shape[0]


# ---------------------------------------------------------------------------
# build (host side, numpy)
# ---------------------------------------------------------------------------

def build_index(doc_tokens: list[np.ndarray], vocab_size: int,
                block: int = bytemap.DEFAULT_BLOCK) -> tuple[WTBCIndex, scdc.SCDCModel]:
    """Build the WTBC for a document collection.

    ``doc_tokens``: one int array of word ids per document, word id 0 reserved
    for the separator '$' (never used inside documents).  Returns the index
    (query ids are *frequency ranks*) and the fitted (s,c)-DC model (for
    mapping original word ids <-> ranks).
    """
    n_docs = len(doc_tokens)
    doc_len = np.array([len(d) for d in doc_tokens], dtype=np.int64)
    flat = np.empty(int(doc_len.sum()) + n_docs, dtype=np.int64)
    pos = 0
    for d in doc_tokens:
        flat[pos:pos + len(d)] = d
        flat[pos + len(d)] = 0                      # '$'
        pos += len(d) + 1
    freqs = np.bincount(flat, minlength=vocab_size)
    model = scdc.fit(freqs, reserve_first=0)
    ranks = model.rank_of_word[flat]
    idx = _build_from_ranks(ranks, model, doc_len, block)
    return idx, model


def build_index_with_model(doc_tokens: list[np.ndarray], model: scdc.SCDCModel,
                           block: int = bytemap.DEFAULT_BLOCK) -> WTBCIndex:
    """Build a (shard) index reusing an already-fitted global (s,c)-DC model.

    Document-sharded deployments fit the code **once over the global
    collection** (codewords must agree across shards so queries are
    shard-agnostic), then each shard indexes its own document range.
    """
    n_docs = len(doc_tokens)
    doc_len = np.array([len(d) for d in doc_tokens], dtype=np.int64)
    flat = np.empty(int(doc_len.sum()) + n_docs, dtype=np.int64)
    pos = 0
    for d in doc_tokens:
        flat[pos:pos + len(d)] = d
        flat[pos + len(d)] = 0
        pos += len(d) + 1
    ranks = model.rank_of_word[flat]
    return _build_from_ranks(ranks, model, doc_len, block)


def _build_from_ranks(ranks: np.ndarray, model: scdc.SCDCModel,
                      doc_len: np.ndarray, block: int) -> WTBCIndex:
    s, c = model.s, model.c
    V = model.vocab_size
    codes, lens = model.codes, model.lens
    tok_codes = codes[ranks]                         # (n, 3) uint8
    tok_lens = lens[ranks]                           # (n,)
    n = len(ranks)

    levels: list[ByteMap] = []
    offset_tables: list[np.ndarray] = []
    keys = np.zeros(n, dtype=np.int64)               # continuer-prefix node key
    for L in range(MAX_LEVELS):
        if L == 0:
            offset_tables.append(np.array([0, n], dtype=np.int64))
            levels.append(bytemap.build(tok_codes[:, 0], block))
            continue
        # key at level L extends the key by the continuer byte at level L-1
        alive_prev = tok_lens > (L - 1)
        keys[alive_prev] = keys[alive_prev] * c + (
            tok_codes[alive_prev, L - 1].astype(np.int64) - s)
        sel = np.flatnonzero(tok_lens > L)
        nspace = c ** L
        if len(sel) == 0:
            offset_tables.append(np.zeros(nspace + 1, dtype=np.int64))
            levels.append(bytemap.build(np.zeros(0, dtype=np.uint8), block))
            continue
        keys_sel = keys[sel]
        order = np.argsort(keys_sel, kind="stable")  # group by node, keep text order
        data = tok_codes[sel[order], L]
        sizes = np.bincount(keys_sel, minlength=nspace)
        offs = np.zeros(nspace + 1, dtype=np.int64)
        np.cumsum(sizes, out=offs[1:])
        offset_tables.append(offs)
        levels.append(bytemap.build(data, block))

    # --- per-word node paths -------------------------------------------------
    node_off = np.zeros((V, MAX_LEVELS), dtype=np.int64)
    prefix = np.zeros(V, dtype=np.int64)
    for L in range(1, MAX_LEVELS):
        has = lens > L
        prefix[has] = prefix[has] * c + (codes[has, L - 1].astype(np.int64) - s)
        node_off[has, L] = offset_tables[L][prefix[has]]

    # base ranks: rank of cw[w, L] at node_off[w, L] within level L
    base_rank = np.zeros((V, MAX_LEVELS), dtype=np.int64)
    for L in range(MAX_LEVELS):
        level_data = np.asarray(levels[L].data)[: int(levels[L].length)]
        order = np.argsort(level_data, kind="stable")
        sorted_vals = level_data[order]
        w = np.flatnonzero(lens > L)
        if len(w) == 0 or len(level_data) == 0:
            continue
        b = codes[w, L]
        base_rank_w = np.empty(len(w), dtype=np.int64)
        # positions of byte value bv, ascending, are order[lo:hi]; rank at an
        # offset is a searchsorted into that slice.  Batch words by byte value.
        for bv in np.unique(b):
            sel = b == bv
            lo = np.searchsorted(sorted_vals, bv, side="left")
            hi = np.searchsorted(sorted_vals, bv, side="right")
            occ_positions = np.sort(order[lo:hi])
            base_rank_w[sel] = np.searchsorted(occ_positions, node_off[w[sel], L])
        base_rank[w, L] = base_rank_w

    root = np.asarray(levels[0].data)[:n]
    sep_pos = np.flatnonzero(root == codes[SEP_RANK, 0]).astype(np.int64)
    assert len(sep_pos) == len(doc_len), "separator count must equal n_docs"

    # document frequencies / occurrences per word rank
    n_docs = len(doc_len)
    doc_ids = np.repeat(np.arange(n_docs, dtype=np.int64), (doc_len + 1).astype(np.int64))
    occ = np.bincount(ranks, minlength=V).astype(np.int64)
    pair = ranks.astype(np.int64) * n_docs + doc_ids
    uniq_words = np.unique(pair) // n_docs
    df = np.bincount(uniq_words, minlength=V).astype(np.int64)

    def as_i32(a):
        assert np.max(a, initial=0) < 2**31
        return jnp.asarray(a.astype(np.int32))

    return WTBCIndex(
        levels=tuple(levels),
        offsets=tuple(as_i32(t) for t in offset_tables),
        cw=jnp.asarray(codes),
        cw_len=as_i32(lens.astype(np.int64)),
        node_off=as_i32(node_off),
        base_rank=as_i32(base_rank),
        sep_pos=as_i32(sep_pos),
        df=as_i32(df),
        occ=as_i32(occ),
        doc_len=as_i32(doc_len),
        n=jnp.int32(n),
        n_docs=jnp.int32(len(doc_len)),
        s=s,
        c=c,
    )


# ---------------------------------------------------------------------------
# document geometry ('$' fast path — paper footnote 2)
# ---------------------------------------------------------------------------

def doc_start(idx: WTBCIndex, d: jnp.ndarray) -> jnp.ndarray:
    """First root position of document d (0-based)."""
    return jnp.where(d == 0, 0, idx.sep_pos[jnp.maximum(d - 1, 0)] + 1)


def doc_end(idx: WTBCIndex, d: jnp.ndarray) -> jnp.ndarray:
    """One past the last content position of doc d (its separator position)."""
    return idx.sep_pos[jnp.clip(d, 0, idx.n_docs - 1)]


def segment_extent(idx: WTBCIndex, d0: jnp.ndarray, d1: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Root range [lo, hi) covering documents [d0, d1)."""
    lo = doc_start(idx, d0)
    hi = jnp.where(d1 >= idx.n_docs, idx.n, doc_start(idx, d1))
    return lo, hi


def doc_of_pos(idx: WTBCIndex, pos: jnp.ndarray) -> jnp.ndarray:
    """Document containing root position pos ( = rank_$(T, pos) )."""
    return jnp.searchsorted(idx.sep_pos, pos, side="left").astype(jnp.int32)


# ---------------------------------------------------------------------------
# count / locate / decode (jit + vmap friendly)
# ---------------------------------------------------------------------------

def count_range(idx: WTBCIndex, w: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """Occurrences of word-rank ``w`` in root range [lo, hi).

    Descends the tree mapping the range through each level with two ranks per
    level; the word's node offsets/base ranks are precomputed.  Uniform 3-level
    unrolled control flow for clean vmap batching.
    """
    a = lo.astype(jnp.int32)
    b = hi.astype(jnp.int32)
    result = jnp.int32(0)
    for L in range(MAX_LEVELS):
        byte = idx.cw[w, L]
        off = idx.node_off[w, L]
        base = idx.base_rank[w, L]
        ra = bytemap.rank(idx.levels[L], byte, off + a) - base
        rb = bytemap.rank(idx.levels[L], byte, off + b) - base
        is_leaf = idx.cw_len[w] == (L + 1)
        result = jnp.where(is_leaf, rb - ra, result)
        a, b = ra, rb
    return result


def count_range_batch(idx: WTBCIndex, words: jnp.ndarray, los: jnp.ndarray,
                      his: jnp.ndarray) -> jnp.ndarray:
    """Batched count: occurrences of ``words[i]`` in root range
    ``[los[i], his[i])`` for a flat batch of M triples; (M,) int32.

    This is the frontier-batched search cores' rank entry point (DESIGN.md
    §6): the whole (M × levels × 2) rank workload goes down in one shot —
    a single fused ``wavelet_descent`` Pallas launch on TPU, one vectorized
    rank batch per level elsewhere (see ``kernels.ops.wavelet_count_batch``).
    """
    from repro.kernels import ops
    return ops.wavelet_count_batch(idx.levels, idx.cw, idx.cw_len,
                                   idx.node_off, idx.base_rank,
                                   words, los, his)


def count_doc(idx: WTBCIndex, w: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """tf of word-rank w in document d."""
    lo, hi = segment_extent(idx, d, d + 1)
    return count_range(idx, w, lo, hi)


def locate_walk(level_select, cwb, off, base, cwl, j, occ, n):
    """The leaf -> root walk of locate, one select per level (paper §2.2
    'locating'), shared by the scalar path, the batched oracle and the
    ``wavelet_locate`` kernel so they cannot drift apart.

    ``level_select(L, byte, k)`` is the lowering's ``bytemap.select`` on level
    L: the position of the k-th (1-based) occurrence of ``byte``, the level's
    length when k is out of range.  The walk passes k = 0 for levels the word
    does not reach and for a dead ``j`` (j < 1 or j > ``occ``), so a lowering
    may skip the work there; a dead ``j`` returns ``n``."""
    live = (j >= 1) & (j <= occ)
    pos = jnp.int32(0)
    for L in range(MAX_LEVELS - 1, -1, -1):
        active = live & (cwl > L)
        # occurrence index within this level's byte stream (global, 1-based):
        # at the leaf (level cwl-1) the j-th occurrence of w is the
        # (base + j)-th occurrence of its stopper byte there
        k = jnp.where(cwl == L + 1, base[L] + j, base[L] + pos + 1)
        p = level_select(L, cwb[L], jnp.where(active, k, 0)) - off[L]
        pos = jnp.where(active, p, pos)
    return jnp.where(live, pos, n).astype(jnp.int32)


def locate(idx: WTBCIndex, w: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """Root position of the j-th (1-based) occurrence of word-rank w;
    ``idx.n`` when j < 1 or j > occ[w]."""
    return locate_walk(
        lambda L, byte, k: bytemap.select(idx.levels[L], byte, k),
        idx.cw[w].astype(jnp.int32), idx.node_off[w], idx.base_rank[w],
        idx.cw_len[w], j, idx.occ[w], idx.n)


def locate_batch(idx: WTBCIndex, words: jnp.ndarray,
                 js: jnp.ndarray) -> jnp.ndarray:
    """Batched locate: root position of the ``js[i]``-th occurrence of
    ``words[i]`` for a flat batch of M pairs; (M,) int32, ``idx.n`` for a
    dead pair (j < 1 or j > occ).  One ``wavelet_locate`` Pallas launch on
    TPU, which spends nothing on dead pairs; the vmapped walk elsewhere
    (see ``kernels.ops.wavelet_locate_batch``)."""
    from repro.kernels import ops
    return ops.wavelet_locate_batch(idx.levels, idx.cw, idx.cw_len,
                                    idx.node_off, idx.base_rank, idx.occ,
                                    idx.n, words, js)


def decode_at(idx: WTBCIndex, pos: jnp.ndarray) -> jnp.ndarray:
    """Word-rank at root position pos (paper §2.2 'decoding').

    Descends with one access + one rank per level, reconstructing the
    (s,c)-DC rank arithmetically from the byte path.
    """
    s, c = idx.s, idx.c
    p = pos.astype(jnp.int32)
    prefix = jnp.int32(0)          # node key at current level
    x = jnp.int32(0)               # accumulated continuer value
    rank_val = jnp.int32(0)
    done = jnp.zeros((), dtype=bool)
    base_k = 0                     # first rank of k-byte band (python, per level)
    width = s
    for L in range(MAX_LEVELS):
        off = idx.offsets[L][prefix]
        b = bytemap.access(idx.levels[L], off + p).astype(jnp.int32)
        is_stop = b < s
        val = jnp.where(is_stop, x * s + b + base_k, 0)
        rank_val = jnp.where(is_stop & ~done, val, rank_val)
        # descend (harmless when done)
        child_rel = (bytemap.rank(idx.levels[L], b.astype(jnp.uint8), off + p)
                     - bytemap.rank(idx.levels[L], b.astype(jnp.uint8), off))
        p = jnp.where(is_stop, p, child_rel)
        prefix = jnp.where(is_stop, prefix, prefix * c + (b - s))
        x = jnp.where(is_stop, x, x * c + (b - s))
        done = done | is_stop
        base_k += width
        width *= c
    return rank_val


def extract(idx: WTBCIndex, lo: jnp.ndarray, length: int) -> jnp.ndarray:
    """Decode ``length`` consecutive word-ranks starting at root position lo
    (snippet extraction; ``length`` static)."""
    return jax.vmap(lambda o: decode_at(idx, lo + o))(jnp.arange(length, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# whole-collection decode — host-side fast path for the DT benchmark
# ---------------------------------------------------------------------------

def decode_all_np(idx: WTBCIndex, model: scdc.SCDCModel) -> np.ndarray:
    """Reconstruct the full token stream (frequency ranks) from the level
    arrays by inverting the stable grouping — the sequential-decompression
    analogue used for the paper's Table-1 'DT' measurement."""
    s, c = idx.s, idx.c
    root = np.asarray(idx.levels[0].data)[: int(idx.levels[0].length)]
    n = len(root)
    x = np.zeros(n, dtype=np.int64)
    lens = np.ones(n, dtype=np.int64)
    bytes_L = root.astype(np.int64)
    alive = np.arange(n)
    prefix = np.zeros(n, dtype=np.int64)
    for L in range(MAX_LEVELS):
        if L > 0:
            level = np.asarray(idx.levels[L].data)[: int(idx.levels[L].length)]
            offs = np.asarray(idx.offsets[L])
            # tokens alive at this level, grouped by node key in text order:
            order = np.argsort(prefix[alive], kind="stable")
            bytes_for = np.empty(len(alive), dtype=np.int64)
            bytes_for[order] = level[: len(alive)]
            bytes_L = bytes_for
            lens[alive] += 1
        cont = bytes_L >= s
        x[alive] = x[alive] * np.where(cont, c, s) + np.where(cont, bytes_L - s, bytes_L)
        prefix_new = prefix[alive] * c + (bytes_L - s)
        keep = alive[cont]
        prefix_next = np.zeros(n, dtype=np.int64)
        prefix_next[keep] = prefix_new[cont]
        prefix = prefix_next
        alive = keep
        if len(alive) == 0:
            break
    bases = np.zeros(MAX_LEVELS + 1, dtype=np.int64)
    base, width = 0, s
    for k in range(1, MAX_LEVELS + 1):
        bases[k] = base
        base, width = base + width, width * c
    return bases[lens] + x


def space_report(idx: WTBCIndex) -> dict[str, int]:
    """Bytes per component — feeds the Table-1 compression-ratio benchmark."""
    def nbytes(a):
        return int(np.asarray(a).nbytes)
    report = {
        # l.length is a scalar on single-host indexes and a per-shard vector
        # on sharded ones — sum over whatever shape it has
        "level_bytes": sum(int(np.asarray(l.length).sum()) for l in idx.levels),
        "rank_counters": sum(nbytes(l.counts) for l in idx.levels),
        "node_offsets": sum(nbytes(o) for o in idx.offsets),
        "codeword_tables": nbytes(idx.cw) + nbytes(idx.cw_len)
                           + nbytes(idx.node_off) + nbytes(idx.base_rank),
        "sep_positions": nbytes(idx.sep_pos),
        "df_occ_doclen": nbytes(idx.df) + nbytes(idx.occ) + nbytes(idx.doc_len),
    }
    report["total"] = sum(report.values())
    return report
