"""WTBC-DRB: ranked retrieval with additional per-word tf bitmaps (paper §3.2).

For every word whose idf exceeds a threshold eps, a bitmap
``1 0^{tf1-1} 1 0^{tf2-1} ...`` encodes its document list and per-document
term frequencies (one bit per *occurrence*; a 1 marks the first occurrence in
a new document).  All bitmaps live concatenated in one packed ``BitVec`` with
a per-word offset table.

Conjunctive queries: candidate generation walks the word with the fewest
unprocessed documents (the paper's triplets ``(wID, nDocs, i)``), locates the
candidate document through the WTBC, verifies/counts the remaining words with
count-range inside the document extent, and skips all cursors past the
candidate.  Bag-of-words: every word's documents are enumerated from its
bitmap and aggregated (here: a vectorized gather/scatter over a doc-score
table + one top-k, the TPU-shaped equivalent of the paper's sort-merge).

Because DRB scores fully materialized candidates, any additive-per-word
measure works — tf-idf (paper) and BM25 (paper §5's noted extension).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitvec, heap as H, wtbc
from repro.core.bitvec import BitVec
from repro.core.ranked import DRResult
from repro.core.wtbc import WTBCIndex

INT32_MAX = jnp.int32(2**31 - 1)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("bv", "bit_off", "has_bm"), meta_fields=("eps",))
@dataclasses.dataclass(frozen=True)
class DRBAux:
    """The paper's 'small additional bitmaps' (its measured overhead: +3%)."""
    bv: BitVec            # concatenated tf bitmaps, word-rank order
    bit_off: jnp.ndarray  # (V+1,) int32
    has_bm: jnp.ndarray   # (V,) bool — idf >= eps (stopwords filtered out)
    eps: float


def build_aux(idx: WTBCIndex, model, doc_tokens: list[np.ndarray],
              eps: float = 1e-6,
              has_bm_override: np.ndarray | None = None) -> DRBAux:
    """Host-side bitmap construction.

    eps follows the paper (1e-6 leaves out only near-universal stopwords).
    ``has_bm_override``: sharded builds pass the *global* stopword decision so
    every shard stores bitmaps for the same word set.
    """
    V = model.vocab_size
    n_docs = len(doc_tokens)
    if has_bm_override is not None:
        has_bm = np.asarray(has_bm_override).copy()
    else:
        df = np.asarray(idx.df)
        idf = np.log(np.maximum(n_docs, 1) / np.maximum(df, 1))
        has_bm = (idf >= eps) & (df > 0)
    has_bm[wtbc.SEP_RANK] = False

    # occurrences of stored words as (word_rank, doc) pairs, sorted
    ranks_list, docs_list = [], []
    for d, toks in enumerate(doc_tokens):
        r = model.rank_of_word[toks]
        keep = has_bm[r]
        ranks_list.append(r[keep].astype(np.int64))
        docs_list.append(np.full(int(keep.sum()), d, dtype=np.int64))
    ranks = np.concatenate(ranks_list) if ranks_list else np.zeros(0, np.int64)
    docs = np.concatenate(docs_list) if docs_list else np.zeros(0, np.int64)
    order = np.lexsort((docs, ranks))
    ranks, docs = ranks[order], docs[order]

    occ_stored = np.bincount(ranks, minlength=V)
    bit_off = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(occ_stored, out=bit_off[1:])
    n_bits = int(bit_off[-1])

    # a bit position is 1 iff its (word, doc) differs from its predecessor's
    pair = ranks * n_docs + docs
    is_one = np.ones(len(pair), dtype=bool)
    is_one[1:] = pair[1:] != pair[:-1]
    set_bits = np.flatnonzero(is_one)
    bv = bitvec.build(set_bits, max(n_bits, 1))
    return DRBAux(
        bv=bv,
        bit_off=jnp.asarray(bit_off.astype(np.int32)),
        has_bm=jnp.asarray(has_bm),
        eps=eps,
    )


def space_report(aux: DRBAux) -> dict[str, int]:
    return {
        "bitmap_bits_bytes": int(np.asarray(aux.bv.words).nbytes),
        "bitmap_counters": int(np.asarray(aux.bv.counts).nbytes),
        "bit_offsets": int(np.asarray(aux.bit_off).nbytes),
    }


# word-relative bitmap ops ----------------------------------------------------

def word_rank1(aux: DRBAux, w: jnp.ndarray, i: jnp.ndarray) -> jnp.ndarray:
    """ones among the first i bits of word w's bitmap (= docs fully passed)."""
    off = aux.bit_off[w]
    return bitvec.rank1(aux.bv, off + i) - bitvec.rank1(aux.bv, off)


def word_select1(aux: DRBAux, w: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """bit position (word-relative) of the j-th 1 in w's bitmap."""
    off = aux.bit_off[w]
    base = bitvec.rank1(aux.bv, off)
    return bitvec.select1(aux.bv, base + j) - off


def word_occ(aux: DRBAux, w: jnp.ndarray) -> jnp.ndarray:
    return aux.bit_off[w + 1] - aux.bit_off[w]


# ---------------------------------------------------------------------------
# conjunctive (AND) — the paper's triplet walk
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "measure", "beam_width",
                                             "max_pops"))
def topk_drb_and(idx: WTBCIndex, aux: DRBAux, words: jnp.ndarray,
                 wmask: jnp.ndarray, measure, *, k: int,
                 idf: jnp.ndarray | None = None,
                 avg_dl: jnp.ndarray | None = None,
                 beam_width: int = 1,
                 max_pops: int | None = None) -> DRResult:
    """Paper §3.2 conjunctive search.  O(df_min) candidate iterations; each
    iteration verifies ``beam_width`` (= P) candidate documents of the rarest
    word at once — P locates, then one fused batched descent for all P×Q
    in-document counts plus the Q cursor-advance prefix counts (DESIGN.md §6).

    ``idf``/``avg_dl`` default to this index's own statistics; distributed
    callers pass the *global* tables so shard scores are comparable.

    Word semantics: a masked word with no bitmap because it is a *stopword*
    (idf < eps) is excluded from the conjunction and from scoring (paper
    footnote 1); a masked word **absent from the collection** (df = 0) makes
    the conjunction empty.

    Beam exactness is trivial here (unlike DR): the walk enumerates and fully
    verifies every candidate regardless of P — P only changes how many are
    in flight per loop trip; consecutive occurrences landing in one document
    are deduplicated before the bounded top-k insert.  The insert keeps the
    total order (score desc, doc asc), so the retained set — score ties at
    the k boundary included — is independent of P and of candidate arrival
    order.  ``beam_width=1`` is step-for-step the paper's triplet walk.

    ``max_pops`` is the anytime budget in *candidate documents examined*
    (the ``pops`` work leaf).  Unlike DR, the walk visits candidates in
    document order, not score order, so certification is all-or-nothing
    (DESIGN.md §11): a completed walk is exact (every slot certified,
    ``bound`` -inf); a budget-stopped walk has examined an arbitrary score
    mix (no slot certified, ``bound`` +inf — an unexamined candidate may
    score anything).
    """
    Q = words.shape[0]
    P = int(beam_width)
    valid = wmask & aux.has_bm[words]
    idf_all = measure.idf(idx) if idf is None else idf
    idf_w = jnp.where(valid, idf_all[words], 0.0).astype(jnp.float32)
    df_w = idx.df[words]
    if avg_dl is None:
        # sum/n_docs (not mean) — doc_len may be zero-padded in sharded stacks
        avg_dl = jnp.sum(idx.doc_len.astype(jnp.float32)) / idx.n_docs.astype(jnp.float32)
    absent = jnp.any(wmask & (df_w == 0))

    # state: per-word occurrence cursor p (0-based, sits on a 1-bit), docs
    # left, candidate-documents-examined counter (the pops work metric)
    p0 = jnp.zeros((Q,), jnp.int32)
    nd0 = jnp.where(valid, df_w, INT32_MAX)
    topk0 = H.topk_make(k)

    def has_work(nd):
        return (jnp.min(nd) > 0) & jnp.any(valid) & ~absent

    def cond(st):
        p, nd, topk, it, cands, padded = st
        ok = has_work(nd) & (it < idx.n_docs + 1)
        if max_pops is not None:
            ok = ok & (cands < max_pops)
        return ok

    def body(st):
        p, nd, topk, it, cands, padded = st
        qstar = jnp.argmin(jnp.where(valid, nd, INT32_MAX))
        wstar = words[qstar]
        occ_star = idx.occ[wstar]
        # candidates: the next P occurrences of the rarest word (their
        # documents are non-decreasing; the first is always a fresh one
        # because cursors sit on document boundaries)
        js = p[qstar] + 1 + jnp.arange(P, dtype=jnp.int32)
        valid_j = js <= occ_star
        pos_j = jax.vmap(lambda j: wtbc.locate(
            idx, wstar, jnp.minimum(j, jnp.maximum(occ_star, 1))))(js)
        d_j = jax.vmap(lambda pp: wtbc.doc_of_pos(idx, pp))(pos_j)
        new_j = valid_j & (d_j != jnp.concatenate(
            [jnp.full((1,), -1, jnp.int32), d_j[:-1]]))
        lo_j, hi_j = wtbc.segment_extent(idx, d_j, d_j + 1)
        d_last = jnp.max(jnp.where(valid_j, d_j, -1))
        hi_last = wtbc.segment_extent(idx, d_last, d_last + 1)[1]
        # one fused batch: P×Q in-document tfs + Q prefix counts at the last
        # candidate's end (the cursor-skip counts).  At P=1 this is the same
        # 2Q rank-descent workload as the classical walk.
        cnt = wtbc.count_range_batch(
            idx,
            jnp.concatenate([jnp.tile(words, P), words]),
            jnp.concatenate([jnp.repeat(lo_j, Q), jnp.zeros((Q,), jnp.int32)]),
            jnp.concatenate([jnp.repeat(hi_j, Q),
                             jnp.broadcast_to(hi_last, (Q,))]))
        tf = cnt[:P * Q].reshape(P, Q) * valid                     # (P, Q)
        cnt_last = cnt[P * Q:]
        present = new_j & jnp.all((tf > 0) | ~valid, axis=-1) & jnp.any(valid)
        score = measure.score(tf, idf_w, idx.doc_len[d_j], avg_dl)  # (P,)

        def ins(tk, x):
            s_, d_, en_ = x
            return H.topk_insert(tk, s_, d_, en_), None

        topk, _ = jax.lax.scan(ins, topk, (score, d_j, present))
        # advance all cursors past the last candidate (paper: recompute
        # triplets)
        p_new = jnp.where(valid, cnt_last, p)
        nd_new = jax.vmap(lambda w_, c_: word_rank1(aux, w_, c_))(words, cnt_last)
        nd_new = jnp.where(valid, df_w - nd_new, INT32_MAX)
        # pad-waste: beam lanes past the rarest word's posting-list end
        # still paid their locate + descent (SearchResults.diagnostics)
        return (p_new, nd_new, topk, it + 1,
                cands + jnp.sum(new_j.astype(jnp.int32)),
                padded + jnp.sum((~valid_j).astype(jnp.int32)))

    p, nd, topk, iters, cands, padded = jax.lax.while_loop(
        cond, body, (p0, nd0, topk0, jnp.int32(0), jnp.int32(0),
                     jnp.int32(0)))
    res = H.topk_sorted(topk)
    found = jnp.sum(res.scores > -jnp.inf).astype(jnp.int32)
    complete = ~has_work(nd)   # stopped because done, not because budgeted
    return DRResult(jnp.where(res.scores > -jnp.inf, res.docs, -1),
                    res.scores, found, iters, cands, jnp.zeros((), bool),
                    padded,
                    certified=(res.scores > -jnp.inf) & complete,
                    bound=jnp.where(complete, H.NEG_INF, jnp.float32(jnp.inf)))


# ---------------------------------------------------------------------------
# bag-of-words (OR) — enumerate every word's documents from its bitmap
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "measure", "max_df_cap"))
def topk_drb_or(idx: WTBCIndex, aux: DRBAux, words: jnp.ndarray,
                wmask: jnp.ndarray, measure, *, k: int, max_df_cap: int,
                idf: jnp.ndarray | None = None,
                avg_dl: jnp.ndarray | None = None) -> DRResult:
    """Paper §3.2 bag-of-words: per word, walk its 1-bits (document starts),
    locate each document's first occurrence through the WTBC, read tf as the
    gap to the next 1, aggregate per document, take the top-k.

    TPU adaptation: the per-word walk is a padded (Q, max_df_cap) gather and
    the aggregation is one scatter-add into a document-score table + one
    ``lax.top_k`` — replacing the paper's sort-merge with dense vector ops.
    The (Q, max_df_cap) first-occurrence locates go down flat in one
    ``wtbc.locate_batch`` call (one ``wavelet_locate`` launch on TPU), with
    j = 0 on the dead lanes (past the word's df, or a masked / stopword
    slot), which the kernel skips.  ``padded`` counts those dead lanes,
    ``Q * max_df_cap`` less the live ones.
    ``max_df_cap`` must be >= max document frequency among the query words.
    """
    Q = words.shape[0]
    n_docs_static = idx.sep_pos.shape[0]
    valid = wmask & aux.has_bm[words]
    idf_all = measure.idf(idx) if idf is None else idf
    idf_w = jnp.where(valid, idf_all[words], 0.0).astype(jnp.float32)
    df_w = jnp.where(valid, idx.df[words], 0)
    occ_w = jax.vmap(lambda w_: word_occ(aux, w_))(words)
    if avg_dl is None:
        avg_dl = jnp.sum(idx.doc_len.astype(jnp.float32)) / idx.n_docs.astype(jnp.float32)

    js = jnp.arange(max_df_cap, dtype=jnp.int32)

    def per_word(q):
        w = words[q]
        # one select1 per document: hoist the word's bitmap base rank (was
        # recomputed per j) and diff consecutive selects instead of running a
        # second select pass for the next-1 positions (§Perf hillclimb 3:
        # 6 counter-block ops per doc -> 1).
        off = aux.bit_off[w]
        base = bitvec.rank1(aux.bv, off)
        sels = jax.vmap(
            lambda j: bitvec.select1(aux.bv, base + j + 1) - off
        )(jnp.arange(max_df_cap + 1, dtype=jnp.int32))                     # (cap+1,)
        sel = sels[:-1]                                                    # i_j
        tf = jnp.where(js + 1 < df_w[q], sels[1:], occ_w[q]) - sel
        return sel, tf

    sel_m, tf_m = jax.vmap(per_word)(jnp.arange(Q))                        # (Q, cap)
    live = (js[None, :] < df_w[:, None]) & valid[:, None]
    # the document's first occurrence is the (i_j + 1)-th of the word
    first_occ = wtbc.locate_batch(
        idx, jnp.repeat(words, max_df_cap),
        jnp.where(live, sel_m + 1, 0).reshape(-1)).reshape(Q, max_df_cap)
    docs_m = jnp.where(live, wtbc.doc_of_pos(idx, first_occ),
                       n_docs_static)                                      # OOB drop
    tf_m = jnp.where(live, tf_m, 0)
    padded = Q * max_df_cap - jnp.sum(live.astype(jnp.int32))

    # per-(word, doc) tf table -> additive measures need tf before transform
    tf_table = jnp.zeros((Q, n_docs_static + 1), jnp.int32)
    tf_table = tf_table.at[jnp.arange(Q)[:, None], docs_m].add(tf_m)
    tf_table = tf_table[:, :n_docs_static]                                 # (Q, N)
    scores = measure.score(tf_table.T, idf_w, idx.doc_len, avg_dl)         # (N,)
    scores = jnp.where(jnp.any(tf_table.T * valid > 0, axis=-1), scores, -jnp.inf)

    top_s, top_d = jax.lax.top_k(scores, k)
    found = jnp.sum(top_s > -jnp.inf).astype(jnp.int32)
    # loop-free dense pass: always exhaustive, hence always fully certified
    return DRResult(jnp.where(top_s > -jnp.inf, top_d, -1).astype(jnp.int32),
                    top_s.astype(jnp.float32), found, jnp.int32(max_df_cap),
                    jnp.int32(max_df_cap), jnp.zeros((), bool), padded,
                    certified=top_s > -jnp.inf, bound=H.NEG_INF)
