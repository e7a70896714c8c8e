"""WTBC-DR: ranked retrieval with *no extra space* (paper §3.1, Algorithm 1).

Best-first search over segments (concatenations of consecutive documents),
driven by a priority queue keyed on segment tf-idf.  The whole collection is
the initial segment; popped multi-document segments are split at the document
boundary nearest their middle; a popped single-document segment is the next
most relevant answer (tf-idf is monotone over concatenation).  Conjunctive
(AND) queries additionally discard any segment in which some query word has
tf = 0.

Faithfulness + two deliberate deviations (DESIGN.md §2):

* Segments are document ranges ``[d0, d1)`` rather than byte ranges; the
  midpoint-'$' search ``select_$(T, rank_$(T, (a+b)/2))`` collapses to integer
  arithmetic on the separator-position array — the paper's own footnote-2
  "faster structure for select_$".
* The paper stores one score per segment and derives the sibling score by
  *float* subtraction.  We store the integer tf vector in the heap payload:
  the sibling's tf is obtained by exact integer subtraction (same saving — one
  ``count_range`` per split, not two) and its score is recomputed from tf, so
  scores carry no accumulated float error and conjunctive emptiness checks
  (tf == 0) are exact.

**Frontier batching** (DESIGN.md §6): each ``while_loop`` iteration pops the
``beam_width`` (= P) best segments at once, computes all P×Q left-child term
frequencies with ONE fused batched descent (``wtbc.count_range_batch``), and
bulk-reinserts the children.  Emission stays exact: a popped singleton is
emitted only if it precedes — in the heap's *total* lex order
``(score desc, d0 asc, d1 desc)``, ties included — everything still pending:
the heap top after the pops and every popped multi-document segment (whose
descendants it strictly bounds); the rest are pushed back.  Because the
order is total, the emission sequence is invariant across beam widths and
insertion schedules, bitwise (tests/test_mega.py pins this).
``beam_width=1`` reproduces the classical one-pop
Algorithm 1 exactly (same pop order, same emission, same heap evolution);
larger P trades a few extra segment expansions for P-wide memory-level
parallelism in the rank workload — the compact-top-k batching lever of
Konow & Navarro's "Faster Compact Top-k Document Retrieval".

**Active-frontier buckets** (this file's padding fix, DESIGN.md §9): a beam
trip at configured width P used to descend P×Q rank rows even when the heap
held a single live segment — at P=64 that made most of the descent traffic
dead padding (BENCH_PR7's 11 ms/call pathology).  Each trip now dispatches
on the *live* frontier width ``min(heap.size, P)`` through a
``lax.switch`` over pow2-bucketed loop bodies (1, 2, 4, …, P), so the
descent batch is sized to the work that exists.  Bucketing is bitwise
inert: ``pop_p`` pops come out as a valid-prefix in the total lex order, a
bucket S always satisfies ``min(size, P) <= S <= P`` (so the popped *set*
per trip is identical at any bucket), and dead lanes never emit or push.
The batched entry point ``topk_dr_batch`` runs one explicitly batched loop
with a *scalar* bucket index (max live width across the batch) — under
``vmap`` a batched switch index would execute every branch and select,
erasing the win, so the switch must stay unbatched.  Pad-waste (dead pop
lanes descended) is surfaced as ``DRResult.padded`` →
``SearchResults.diagnostics``.

The full search is one jitted ``lax.while_loop`` per query row; batched
queries share one loop whose trip count is the max over rows (finished rows
are mask-frozen exactly as ``vmap`` of a ``while_loop`` would).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import heap as H
from repro.core import wtbc
from repro.core.wtbc import WTBCIndex


class DRResult(NamedTuple):
    docs: jnp.ndarray    # (k,) int32, -1 padded, sorted by descending score
    scores: jnp.ndarray  # (k,) float32, -inf padded
    n_found: jnp.ndarray # () int32
    iters: jnp.ndarray   # () int32 — while-loop trips (work metric for §Perf)
    # () int32 — segments actually popped (== iters at beam_width=1); the
    # beam's emitted-doc overhead metric is pops(P) / pops(1)
    pops: jnp.ndarray | None = None
    # () bool — a heap push was dropped at capacity: the ranking may be
    # inexact and the caller must not trust it silently (DESIGN.md §6)
    overflowed: jnp.ndarray | None = None
    # () int32 — dead pop lanes whose descent rows were still computed
    # (pad-waste): pops + padded = beam lanes processed.  The active-frontier
    # buckets keep this near zero; DRB/OR counts its dead (word, document)
    # lanes; None on cores without padding (mega, brute force).
    padded: jnp.ndarray | None = None
    # (k,) bool — anytime certification (DESIGN.md §11): slot i is certified
    # iff its key lex-beats the pending bound at the stopping point, i.e. it
    # provably equals the exact oracle's slot i.  All-True whenever the
    # search ran to completion; certified bits always form a prefix.
    certified: jnp.ndarray | None = None
    # () float32 — score upper bound on every document NOT in ``docs``
    # (the lex-max pending segment score at stop); -inf when the frontier
    # was exhausted, i.e. nothing relevant remains.
    bound: jnp.ndarray | None = None


def count_words_range(idx: WTBCIndex, words: jnp.ndarray,
                      lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    """tf of each query word in root range [lo, hi); (Q,) int32.

    One batched descent for the whole word set (kernels-on-TPU: a single
    fused ``wavelet_descent`` launch)."""
    Q = words.shape[0]
    return wtbc.count_range_batch(idx, words, jnp.broadcast_to(lo, (Q,)),
                                  jnp.broadcast_to(hi, (Q,)))


def _frontier_buckets(P: int) -> tuple[int, ...]:
    """Pow2 frontier-width buckets 1, 2, 4, …, capped by (and always
    including) the configured beam width P."""
    ws = []
    w = 1
    while w < P:
        ws.append(w)
        w *= 2
    ws.append(P)
    return tuple(ws)


def _tree_select(mask, new, old):
    """Per-row freeze: where ``mask`` (B,) is False, keep ``old`` — the same
    per-row select ``vmap`` of a ``while_loop`` lowers its body to."""
    def sel(a, b):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim))
        return jnp.where(m, a, b)
    return jax.tree.map(sel, new, old)


def _dr_row_init(idx, words, wmask, idf_w, *, k, conjunctive, heap_cap):
    """Per-row loop state: (heap, out_docs, out_scores, n_out, it, pops,
    padded).  ``words``/``wmask``/``idf_w`` are one query row (Q,)."""
    Q = words.shape[0]
    n_docs = idx.n_docs
    lo0, hi0 = wtbc.segment_extent(idx, jnp.int32(0), n_docs)
    tf0 = count_words_range(idx, words, lo0, hi0) * wmask
    score0 = tf0.astype(jnp.float32) @ idf_w
    if conjunctive:
        en0 = jnp.all((tf0 > 0) | ~wmask, axis=-1) & jnp.any(wmask)
    else:
        en0 = score0 > 0.0
    pay0 = jnp.concatenate([jnp.stack([jnp.int32(0), n_docs]), tf0])
    hp = H.make(heap_cap, 2 + Q)
    hp = H.push(hp, score0, pay0, en0)
    # emission order is already globally sorted; track an explicit write
    # cursor.  Slot k is a trash slot for beam emissions past the k budget.
    out_docs = jnp.full((k + 1,), -1, jnp.int32)
    out_scores = jnp.full((k + 1,), -jnp.inf, jnp.float32)
    return (hp, out_docs, out_scores, jnp.int32(0), jnp.int32(0),
            jnp.int32(0), jnp.int32(0))


def _dr_row_cond(st, *, k, max_pops):
    hp, _, _, n_out, _, pops, _ = st
    ok = (n_out < k) & (hp.size > 0)
    if max_pops is not None:
        ok = ok & (pops < max_pops)
    return ok


def _dr_row_body(st, words, wmask, idf_w, *, idx, S, k, conjunctive):
    """One beam trip of one query row at (bucketed) frontier width ``S``.

    Bitwise-identical to running the trip at any width in [min(size, P), P]:
    pops come out as a valid-prefix in the total lex order, and dead lanes
    (valid False) neither emit nor push — only ``padded`` sees them.
    """
    hp, out_docs, out_scores, n_out, it, pops, padded = st
    Q = words.shape[0]

    def seg_score(tf):
        # (..., Q) int32 -> (...,) float32; matvec == the one-pop jnp.dot
        return tf.astype(jnp.float32) @ idf_w

    def seg_valid(tf, score):
        if conjunctive:
            return jnp.all((tf > 0) | ~wmask, axis=-1) & jnp.any(wmask)
        return score > 0.0

    s_p, pay, valid, hp = H.pop_p(hp, S)          # scores descending
    d0, d1, tf = pay[:, 0], pay[:, 1], pay[:, 2:]
    single = valid & ((d1 - d0) == 1)
    multi = valid & ~single

    # exact-emission bound: everything still pending is lex-bounded by
    # the heap top after the S pops and the popped multis' own keys — a
    # segment's key (score desc, d0 asc, d1 desc) strictly bounds every
    # descendant's (score is monotone over concatenation; on score ties
    # a left child keeps d0 but shrinks d1, a right child grows d0).  A
    # popped singleton that lex-beats the bound is the globally next
    # answer *including tie order*, so the emission sequence is the same
    # for every beam width; the rest go back into the heap.
    cs = jnp.concatenate([s_p, hp.scores[:1]])
    c0 = jnp.concatenate([d0, hp.payload[0][:1]])
    c1 = jnp.concatenate([d1, hp.payload[1][:1]])
    cv = jnp.concatenate([multi, (hp.size > 0)[None]])
    j = H.lex_argmax(cs, c0, c1, cv)
    emit = single & (~jnp.any(cv)
                     | H.lex_gt(s_p, d0, d1, cs[j], c0[j], c1[j]))
    slot = n_out + jnp.cumsum(emit.astype(jnp.int32)) - 1
    write = emit & (slot < k)
    at = jnp.where(write, slot, k)
    out_docs = out_docs.at[at].set(jnp.where(write, d0, out_docs[at]))
    out_scores = out_scores.at[at].set(
        jnp.where(write, s_p, out_scores[at]))
    n_out = jnp.minimum(n_out + jnp.sum(emit.astype(jnp.int32)), k)

    # split every popped multi at the doc boundary nearest its middle;
    # all S×Q left-child tfs in ONE batched descent (degenerate math on
    # masked lanes is discarded by the push enables)
    mid = (d0 + d1) // 2
    lo1, hi1 = wtbc.segment_extent(idx, d0, mid)
    tf1 = wtbc.count_range_batch(
        idx, jnp.tile(words, S), jnp.repeat(lo1, Q),
        jnp.repeat(hi1, Q)).reshape(S, Q) * wmask
    tf2 = tf - tf1
    s1, s2 = seg_score(tf1), seg_score(tf2)
    pay1 = jnp.concatenate([jnp.stack([d0, mid], axis=1), tf1], axis=1)
    pay2 = jnp.concatenate([jnp.stack([mid, d1], axis=1), tf2], axis=1)
    # bulk reinsert, parent-major (left, right, unemitted single): at
    # S=1 this is push(left), push(right) — the one-pop order exactly.
    # (At S=1 the popped item IS the heap max, so a popped singleton
    # always clears the threshold and the re-push slot is statically
    # dead — drop it to keep the one-pop bucket at the classical cost.)
    slots = ([s1, s2], [pay1, pay2],
             [multi & seg_valid(tf1, s1), multi & seg_valid(tf2, s2)])
    if S > 1:
        slots[0].append(s_p)
        slots[1].append(pay)
        slots[2].append(single & ~emit)
    W = len(slots[0])
    push_s = jnp.stack(slots[0], axis=1).reshape(W * S)
    push_pay = jnp.stack(slots[1], axis=1).reshape(W * S, 2 + Q)
    push_en = jnp.stack(slots[2], axis=1).reshape(W * S)
    hp = H.push_many(hp, push_s, push_pay, push_en)
    nv = jnp.sum(valid.astype(jnp.int32))
    return (hp, out_docs, out_scores, n_out, it + 1, pops + nv,
            padded + (S - nv))


def _bucket_index(n_live, buckets):
    """Scalar index of the smallest bucket >= n_live (n_live >= 1)."""
    return sum((n_live > w).astype(jnp.int32) for w in buckets[:-1])


def _anytime_finalize(hp: H.Heap, out_docs, out_scores, n_out, *, k: int,
                      harvest: bool):
    """Anytime epilogue of one row (DESIGN.md §11): harvest + certify.

    Runs after the while_loop on the per-row heap state.  Two steps:

    1. **Harvest** (only when an anytime budget was in play): fill the
       remaining output slots best-k-so-far with the lex-greatest pending
       *singleton* segments — real documents with exact scores, just not yet
       proven to beat every hidden document.  When the budget never bound,
       the loop only exits with ``n_out == k`` or an empty heap, so the
       harvest writes nothing and every leaf is bitwise what it was.
    2. **Certify**: the pending bound is the lex-max key over everything
       still in the heap (multis bound all their descendants by key
       monotonicity; singletons bound themselves).  A slot is certified iff
       its own key ``(score, d, d+1)`` lex-beats that bound — emitted slots
       always do (the emission rule already proved them against the whole
       pending set, whose keys only decrease); harvested slots only when no
       hidden document can outrank them.  ``overflowed`` voids the bound (a
       dropped push's descendants are unaccounted for), so it vetoes
       certification.

    Returns ``(out_docs, out_scores, n_out, certified (k,), bound ())``.
    """
    s, d0, d1 = hp.scores, hp.payload[0], hp.payload[1]
    valid = jnp.arange(hp.cap, dtype=jnp.int32) < hp.size
    single = valid & ((d1 - d0) == 1)
    remaining = valid

    if harvest:
        def step(_, st):
            out_docs, out_scores, n_out, sing = st
            j = H.lex_argmax(s, d0, d1, sing)
            write = jnp.any(sing) & (n_out < k)
            at = jnp.where(write, n_out, k)
            out_docs = out_docs.at[at].set(
                jnp.where(write, d0[j], out_docs[at]))
            out_scores = out_scores.at[at].set(
                jnp.where(write, s[j], out_scores[at]))
            sing = sing.at[j].set(sing[j] & ~write)
            return out_docs, out_scores, n_out + write.astype(jnp.int32), sing

        out_docs, out_scores, n_out, left = jax.lax.fori_loop(
            0, k, step, (out_docs, out_scores, n_out, single))
        remaining = (valid & ~single) | left

    has_rem = jnp.any(remaining)
    j = H.lex_argmax(s, d0, d1, remaining)
    bnd_s = jnp.where(has_rem, s[j], H.NEG_INF)
    bnd_d0 = jnp.where(has_rem, d0[j], H.INT32_MAX)
    bnd_d1 = jnp.where(has_rem, d1[j], H.INT32_MIN)
    filled = jnp.arange(out_docs.shape[0], dtype=jnp.int32) < n_out
    certified = filled & ~hp.overflowed & H.lex_gt(
        out_scores, out_docs, out_docs + 1, bnd_s, bnd_d0, bnd_d1)
    return out_docs, out_scores, n_out, certified[:k], bnd_s


@functools.partial(jax.jit,
                   static_argnames=("k", "conjunctive", "heap_cap", "max_pops",
                                    "beam_width"))
def topk_dr(idx: WTBCIndex, words: jnp.ndarray, wmask: jnp.ndarray,
            idf: jnp.ndarray, *, k: int, conjunctive: bool,
            heap_cap: int, max_pops: int | None = None,
            beam_width: int = 1) -> DRResult:
    """Algorithm 1, frontier-batched.  ``words`` (Q,) word-ranks, ``wmask``
    (Q,) valid-word mask, ``idf`` (V,) precomputed idf table.  ``heap_cap``
    >= 2*n_docs + 2 makes the search exact (the implicit split tree has
    < 2*n_docs nodes; beam re-pushes never exceed that bound because a
    segment occupies at most one heap slot at a time).

    ``max_pops`` is the any-time budget (straggler mitigation, DESIGN.md §4):
    the search stops once that many segments have been popped and returns the
    documents emitted so far — every emitted document is still exactly
    ranked.  With ``beam_width`` = P > 1 the budget is enforced at iteration
    granularity (overshoot < P).

    ``beam_width`` = P pops *up to* P segments per iteration and batches
    their rank workload into one fused call sized to the live frontier
    (pow2 buckets — see the module docstring); P=1 is the classical exact
    pop order.  Results are bitwise-identical across widths and buckets.
    """
    P = int(beam_width)
    idf_w = jnp.where(wmask, idf[words], 0.0).astype(jnp.float32)
    st0 = _dr_row_init(idx, words, wmask, idf_w, k=k,
                       conjunctive=conjunctive, heap_cap=heap_cap)

    def cond(st):
        return _dr_row_cond(st, k=k, max_pops=max_pops)

    buckets = _frontier_buckets(P)

    def mk(S):
        return lambda st: _dr_row_body(st, words, wmask, idf_w, idx=idx,
                                       S=S, k=k, conjunctive=conjunctive)

    bodies = [mk(S) for S in buckets]
    if len(buckets) == 1:
        body = bodies[0]
    else:
        def body(st):
            # scalar bucket index: plain jit executes ONE branch per trip
            n_live = jnp.minimum(st[0].size, P)
            return jax.lax.switch(_bucket_index(n_live, buckets), bodies, st)

    hp, out_docs, out_scores, n_out, iters, pops, padded = \
        jax.lax.while_loop(cond, body, st0)
    out_docs, out_scores, n_out, certified, bound = _anytime_finalize(
        hp, out_docs, out_scores, n_out, k=k, harvest=max_pops is not None)
    return DRResult(out_docs[:k], out_scores[:k], n_out, iters, pops,
                    hp.overflowed, padded, certified, bound)


@functools.partial(jax.jit,
                   static_argnames=("k", "conjunctive", "heap_cap", "max_pops",
                                    "beam_width"))
def topk_dr_batch(idx: WTBCIndex, words: jnp.ndarray, wmask: jnp.ndarray,
                  idf: jnp.ndarray, *, k: int, conjunctive: bool,
                  heap_cap: int, max_pops: int | None = None,
                  beam_width: int = 1) -> DRResult:
    """Batched queries: ``words``/``wmask`` are (B, Q).

    One explicitly batched loop instead of ``vmap(topk_dr)``: the loop body
    is the *vmapped* per-row trip (so row math — and therefore every result
    leaf — is bitwise what the vmapped serial core produced), but the
    frontier bucket is chosen by a **scalar** index, the max live width
    across still-live rows.  Under ``vmap`` a per-row ``lax.switch`` index
    is batched, which executes every branch and selects — paying for all
    buckets at once; hoisting the dispatch above the vmapped body keeps the
    one-branch-per-trip property the padding fix exists for.  Rows that
    finish early are mask-frozen per trip, exactly the select that
    ``vmap(while_loop)`` lowers to, so per-row ``iters``/``pops`` stay
    row-exact.

    ``padded`` is the one leaf that reflects the batched SCHEDULE rather
    than the per-row computation: a row whose frontier is narrower than the
    batch's max live width pops padded lanes the serial per-row bucket
    would avoid, so batch ``padded`` >= serial ``padded`` row-wise (every
    other leaf is bitwise equal).
    """
    B, Q = words.shape
    P = int(beam_width)
    idf_w = jnp.where(wmask, idf[words], 0.0).astype(jnp.float32)   # (B, Q)
    st0 = jax.vmap(lambda w, m, iw: _dr_row_init(
        idx, w, m, iw, k=k, conjunctive=conjunctive, heap_cap=heap_cap))(
            words, wmask, idf_w)

    def lives(st):
        return jax.vmap(lambda s: _dr_row_cond(s, k=k, max_pops=max_pops))(st)

    def cond(st):
        return jnp.any(lives(st))

    buckets = _frontier_buckets(P)

    def mk(S):
        row = lambda s, w, m, iw: _dr_row_body(s, w, m, iw, idx=idx, S=S,
                                               k=k, conjunctive=conjunctive)

        def body_S(st):
            live = lives(st)
            new = jax.vmap(row)(st, words, wmask, idf_w)
            return _tree_select(live, new, st)
        return body_S

    bodies = [mk(S) for S in buckets]
    if len(buckets) == 1:
        body = bodies[0]
    else:
        def body(st):
            # the bucket index is a SCALAR (max live width over the batch):
            # every row pops its full min(size, P) this trip — identical
            # pop set — while the descent batch shrinks to the widest live
            # frontier instead of the configured P
            live = lives(st)
            n_live = jnp.max(jnp.where(live, jnp.minimum(st[0].size, P), 0))
            return jax.lax.switch(_bucket_index(n_live, buckets), bodies, st)

    hp, out_docs, out_scores, n_out, iters, pops, padded = \
        jax.lax.while_loop(cond, body, st0)
    out_docs, out_scores, n_out, certified, bound = jax.vmap(
        functools.partial(_anytime_finalize, k=k,
                          harvest=max_pops is not None))(
        hp, out_docs, out_scores, n_out)
    return DRResult(out_docs[:, :k], out_scores[:, :k], n_out, iters, pops,
                    hp.overflowed, padded, certified, bound)


# ---------------------------------------------------------------------------
# brute-force oracle (tests + benchmark ground truth)
# ---------------------------------------------------------------------------

def topk_bruteforce(idx: WTBCIndex, words, wmask, idf, *, k: int,
                    conjunctive: bool) -> DRResult:
    """Score every document directly with count_range — O(N*Q) oracle."""
    n_docs = int(idx.n_docs)
    words = jnp.asarray(words)
    wmask = jnp.asarray(wmask)
    idf_w = jnp.where(wmask, idf[words], 0.0)

    def score_doc(d):
        lo, hi = wtbc.segment_extent(idx, d, d + 1)
        tf = count_words_range(idx, words, lo, hi) * wmask
        s = jnp.dot(tf.astype(jnp.float32), idf_w)
        if conjunctive:
            ok = jnp.all((tf > 0) | ~wmask) & jnp.any(wmask)
        else:
            ok = s > 0
        return jnp.where(ok, s, -jnp.inf)

    scores = jax.lax.map(score_doc, jnp.arange(n_docs, dtype=jnp.int32))
    top_s, top_d = jax.lax.top_k(scores, k)
    found = jnp.sum(top_s > -jnp.inf).astype(jnp.int32)
    top_d = jnp.where(top_s > -jnp.inf, top_d, -1)
    return DRResult(top_d.astype(jnp.int32), top_s, found, jnp.int32(n_docs),
                    jnp.int32(n_docs), jnp.zeros((), bool),
                    certified=top_s > -jnp.inf, bound=H.NEG_INF)
