"""The comparison that decides ``correct``: served answers against the
reference, three numbers, each against its limit.

* ``wrong_hits``: answers that return a document the reference does not
  hold eligible, return one twice, or return fewer than ``min(k, eligible)``
  documents.  Exact: limit 0.
* ``score_gap``: the widest relative gap between a served score and the
  reference's score of that document.
* ``rank_gap``: the widest relative amount by which an unreturned eligible
  document's reference score exceeds the least reference score among the
  returned ones (0 when the returned set is a true top-k).

The limits are set per cell in its traffic file, from the
readings of the program (lower) and of the control (upper): the reference
itself computed in bfloat16 (``reference.rank(..., dtype=bfloat16)``), the
precision below the float32 the engine scores in.
"""
from __future__ import annotations

import numpy as np

from lib import reference

NUMBERS = ("wrong_hits", "score_gap", "rank_gap")


def numbers(coll, answers, profile: dict, *, control_dtype=None) -> dict:
    """``answers``: ``[(query, docs, scores), ...]`` with the returned
    (n_found,) documents and scores.  With ``control_dtype`` the answers are
    replaced by the reference's own, computed in that precision."""
    k = profile["k"]
    wrong = 0
    score_gap = rank_gap = 0.0
    for query, docs, scores in answers:
        ref = reference.rank(coll, query, mode=profile["mode"],
                             strategy=profile["strategy"],
                             measure=profile["measure"])
        if control_dtype is not None:
            low = reference.rank(coll, query, mode=profile["mode"],
                                 strategy=profile["strategy"],
                                 measure=profile["measure"],
                                 dtype=control_dtype)
            docs, scores = low.top(k)
        docs = np.asarray(docs, np.int64)
        scores = np.asarray(scores, np.float64)
        pos = np.searchsorted(ref.docs, docs)
        inside = pos < len(ref.docs)
        held = np.zeros(len(docs), dtype=bool)
        held[inside] = ((ref.docs[pos[inside]] == docs[inside])
                        & ref.eligible[pos[inside]])
        n_eligible = int(ref.eligible.sum())
        if (not np.all(held) or len(set(docs.tolist())) != len(docs)
                or len(docs) != min(k, n_eligible)):
            wrong += 1
            continue
        if not len(docs):
            continue
        r = ref.scores[pos]
        score_gap = max(score_gap, float(np.max(np.abs(scores - r)
                                                / np.abs(r))))
        rest = ref.eligible.copy()
        rest[pos] = False
        if rest.any():
            least = float(r.min())
            over = (float(ref.scores[rest].max()) - least) / abs(least)
            rank_gap = max(rank_gap, over)
    return {"wrong_hits": wrong, "score_gap": score_gap,
            "rank_gap": rank_gap}


def verdict(nums: dict, limits: dict) -> tuple[bool, list[list]]:
    """(correct, [[name, number, limit], ...]): each number the cell's
    ``limits`` name at or under its limit.  A cell leaves out a number its
    control cannot move (``rank_gap`` where no answer leaves an eligible
    document out)."""
    rows = [[name, nums[name], limits[name]] for name in NUMBERS
            if name in limits]
    return all(v <= lim for _, v, lim in rows), rows
