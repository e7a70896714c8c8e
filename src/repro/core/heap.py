"""Fixed-capacity array binary heaps, jit-compatible (lax.while_loop sifts).

Algorithm 1 of the paper is driven by a priority queue of text segments.  A
pointer-based heap does not exist in JAX-land; we use the classical implicit
binary heap over a pre-allocated score array plus an int32 payload matrix.
Pushes/pops are O(log cap) with dynamic index updates — the whole retrieval
loop stays on-device with no host round trips.

All operations take and return the state tuple
``(scores, payload, size, overflowed)``:
  scores     (cap,)   float32, max-heap ordered prefix [0, size)
  payload    P x (cap,) int32 — one array per payload column (a (cap, P)
             matrix with P of 2-10 pads its minor axis to the TPU's 128
             lanes, 13-64x the bytes every sift step moves)
  size       ()       int32
  overflowed ()       bool — any enabled push ever hit a full heap

**Total priority order.**  The heap orders elements by the lexicographic key
``(score desc, payload[0] asc, payload[1] desc)`` (with the payload columns
dropped for narrower payloads).  Algorithm 1 stores segments ``[d0, d1)`` as
``payload[:2]``, and distinct segments always have distinct keys — so the
order is *total*: pop order does not depend on insertion order, and therefore
not on the beam width or batching schedule that produced the insertions.
Score ties (duplicate tf patterns across documents) resolve toward the lower
``d0``, matching ``lax.top_k`` / ``TopK`` doc-id tie-breaking, so every layer
of the stack agrees on tie order (DESIGN.md §8).

``enable`` flags make pushes/pops conditional without ``lax.cond`` branches on
the large state (disabled ops are no-ops with the same cost).

A push against a full heap *drops the element* (the search stays total but may
become inexact); ``overflowed`` latches that event so callers — `DRResult` /
`SearchResults.diagnostics` — can surface it instead of silently returning
corrupted rankings (DESIGN.md §6).

``pop_p`` / ``push_many`` are the frontier-batched (beam) entry points: P
ordered pops and a bulk reinsert per search iteration, so Algorithm 1's rank
workload can be batched P-wide between heap interactions (DESIGN.md §6).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)
INT32_MAX = jnp.int32(2**31 - 1)
INT32_MIN = jnp.int32(-2**31)


def lex_gt(sa, a0, a1, sb, b0, b1):
    """Strict elementwise comparison in the total priority order
    ``(score desc, d0 asc, d1 desc)``: True where key A precedes key B."""
    return (sa > sb) | ((sa == sb) & ((a0 < b0) | ((a0 == b0) & (a1 > b1))))


def lex_argmax(s, d0, d1, valid):
    """Index (last axis) of the lex-greatest valid ``(s, d0, d1)`` entry:
    max score, then min d0 among score ties, then max d1.  Three masked
    reductions — the dense-pool analogue of a heap top (core/mega.py).
    All-invalid rows return index 0; callers mask with ``valid.any()``."""
    s_ = jnp.where(valid, s, NEG_INF)
    c = valid & (s_ == jnp.max(s_, axis=-1, keepdims=True))
    d0_ = jnp.where(c, d0, INT32_MAX)
    c = c & (d0_ == jnp.min(d0_, axis=-1, keepdims=True))
    return jnp.argmax(jnp.where(c, d1, INT32_MIN), axis=-1).astype(jnp.int32)


def _prio_gt(sc, pl, i, j):
    """Heap-internal: element ``i`` strictly precedes element ``j`` under the
    total order, on whatever payload columns this heap carries."""
    W = len(pl)
    z = jnp.int32(0)
    a0, b0 = (pl[0][i], pl[0][j]) if W >= 1 else (z, z)
    a1, b1 = (pl[1][i], pl[1][j]) if W >= 2 else (z, z)
    # payload col 1 is d1: *descending* in the order (see module docstring)
    return lex_gt(sc[i], a0, a1, sc[j], b0, b1)


class Heap(NamedTuple):
    scores: jnp.ndarray      # (cap,) float32
    payload: tuple           # P x (cap,) int32, one array per column
    size: jnp.ndarray        # () int32
    overflowed: jnp.ndarray  # () bool

    @property
    def cap(self) -> int:
        return self.scores.shape[0]


def make(cap: int, payload_width: int) -> Heap:
    return Heap(
        scores=jnp.full((cap,), NEG_INF, dtype=jnp.float32),
        payload=tuple(jnp.zeros((cap,), dtype=jnp.int32)
                      for _ in range(payload_width)),
        size=jnp.int32(0),
        overflowed=jnp.zeros((), dtype=bool),
    )


def push(h: Heap, score: jnp.ndarray, pay: jnp.ndarray,
         enable: jnp.ndarray | bool = True) -> Heap:
    """Insert (score, pay); no-op when ``enable`` is False or heap is full.

    A capacity-dropped enabled push latches ``overflowed``."""
    want = jnp.asarray(enable)
    enable = want & (h.size < h.cap)
    overflowed = h.overflowed | (want & (h.size >= h.cap))
    scores, payload, size, _ = h
    at = jnp.where(enable, size, jnp.int32(0))
    scores = scores.at[at].set(jnp.where(enable, score, scores[at]))
    payload = tuple(c.at[at].set(jnp.where(enable, pay[k], c[at]))
                    for k, c in enumerate(payload))

    def cond(st):
        i, sc, pl = st
        par = (i - 1) // 2
        return (i > 0) & _prio_gt(sc, pl, i, par)

    def body(st):
        i, sc, pl = st
        par = (i - 1) // 2
        si, sp = sc[i], sc[par]
        sc = sc.at[i].set(sp).at[par].set(si)
        pl = tuple(c.at[i].set(c[par]).at[par].set(c[i]) for c in pl)
        return par, sc, pl

    i0 = jnp.where(enable, size, jnp.int32(0))
    _, scores, payload = jax.lax.while_loop(cond, body, (i0, scores, payload))
    return Heap(scores, payload, size + enable.astype(jnp.int32), overflowed)


def pop(h: Heap) -> tuple[jnp.ndarray, jnp.ndarray, Heap]:
    """Remove and return the max element.  Caller guards ``size > 0``."""
    scores, payload, size, overflowed = h
    top_s, top_p = scores[0], jnp.stack([c[0] for c in payload])
    last = jnp.maximum(size - 1, 0)
    scores = scores.at[0].set(scores[last]).at[last].set(NEG_INF)
    payload = tuple(c.at[0].set(c[last]) for c in payload)
    size = last

    cap = h.cap

    def children(i, sc, pl):
        l, r = 2 * i + 1, 2 * i + 2
        # clamp the *index* (not the score) so lex gathers stay in bounds;
        # validity masks make the clamped reads inert
        lm, rm = jnp.minimum(l, cap - 1), jnp.minimum(r, cap - 1)
        return lm, rm, l < size, r < size

    def cond(st):
        i, sc, pl = st
        lm, rm, lv, rv = children(i, sc, pl)
        return ((lv & _prio_gt(sc, pl, lm, i))
                | (rv & _prio_gt(sc, pl, rm, i)))

    def body(st):
        i, sc, pl = st
        lm, rm, lv, rv = children(i, sc, pl)
        r_wins = rv & (~lv | _prio_gt(sc, pl, rm, lm))
        c = jnp.where(r_wins, rm, lm)
        si, scc = sc[i], sc[c]
        sc = sc.at[i].set(scc).at[c].set(si)
        pl = tuple(col.at[i].set(col[c]).at[c].set(col[i]) for col in pl)
        return c, sc, pl

    _, scores, payload = jax.lax.while_loop(cond, body, (jnp.int32(0), scores, payload))
    return top_s, top_p, Heap(scores, payload, size, overflowed)


# ---------------------------------------------------------------------------
# frontier batching (beam search, DESIGN.md §6)
# ---------------------------------------------------------------------------

def pop_p(h: Heap, p: int) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, Heap]:
    """Pop the ``p`` best elements (``p`` static).

    Returns ``(scores (p,), payloads (p, W), valid (p,), heap)``; pops past
    the current size are masked out (score -inf, valid False).  Pops come out
    in the total lex order — the same flattened sequence for every ``p``,
    which the beam emission rule and its schedule-invariance tests rely on.
    ``pop`` on an empty heap is already a structural no-op (the sift guard
    sees size 0), so no per-step branching is needed.
    """
    size0 = h.size

    def step(hp, _):
        s, pay, hp = pop(hp)
        return hp, (s, pay)

    h, (scores, payloads) = jax.lax.scan(step, h, None, length=p)
    valid = jnp.arange(p, dtype=jnp.int32) < size0
    return jnp.where(valid, scores, NEG_INF), payloads, valid, h


def push_many(h: Heap, scores: jnp.ndarray, pays: jnp.ndarray,
              enable: jnp.ndarray) -> Heap:
    """Bulk insert: ``scores (m,)``, ``pays (m, W)``, ``enable (m,)``.

    Sequential gated pushes in array order (the order is observable through
    pop tie-breaking, so beam callers keep it deterministic)."""

    def step(hp, x):
        s, pay, en = x
        return push(hp, s, pay, en), None

    h, _ = jax.lax.scan(step, h, (scores, pays, enable))
    return h


# ---------------------------------------------------------------------------
# bounded top-k result set (k is tiny: argmin replace beats a heap on VPU)
# ---------------------------------------------------------------------------

class TopK(NamedTuple):
    scores: jnp.ndarray  # (k,) float32, -inf padded
    docs: jnp.ndarray    # (k,) int32


def topk_make(k: int) -> TopK:
    return TopK(jnp.full((k,), NEG_INF, jnp.float32), jnp.full((k,), -1, jnp.int32))


def topk_insert(t: TopK, score: jnp.ndarray, doc: jnp.ndarray,
                enable: jnp.ndarray | bool = True) -> TopK:
    """Keep the k best pairs under the total order (score desc, doc asc).

    The retained *set* is insertion-order invariant, ties included: the
    replaced slot is the lex-least (min score, then max doc) and a candidate
    enters iff it lex-beats that slot — so a score tie at the boundary always
    resolves toward the lower doc id, matching the heap/`lax.top_k` order."""
    m = jnp.min(t.scores)
    at_min = t.scores == m
    worst = jnp.argmax(jnp.where(at_min, t.docs, INT32_MIN))
    better = jnp.asarray(enable) & (
        (score > m) | ((score == m) & (doc < t.docs[worst])))
    return TopK(
        scores=t.scores.at[worst].set(jnp.where(better, score, t.scores[worst])),
        docs=t.docs.at[worst].set(jnp.where(better, doc, t.docs[worst])),
    )


def topk_sorted(t: TopK) -> TopK:
    """Descending by score; ties by ascending doc id (deterministic output)."""
    order = jnp.lexsort((t.docs, -t.scores))
    return TopK(t.scores[order], t.docs[order])
