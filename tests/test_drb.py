"""WTBC-DRB (bitmaps) vs brute-force oracles — tf-idf and BM25."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core import drb, ranked, scoring, wtbc
from tests.test_ranked import check_topk_equal, query_pool


def bruteforce_measure(idx, words, wmask, measure, k, conjunctive):
    """Generic oracle for any additive measure (incl. BM25)."""
    idf = measure.idf(idx)
    avg_dl = jnp.sum(idx.doc_len.astype(jnp.float32)) / idx.n_docs
    idf_w = jnp.where(wmask, idf[words], 0.0)

    def score_doc(d):
        lo, hi = wtbc.segment_extent(idx, d, d + 1)
        tf = ranked.count_words_range(idx, words, lo, hi) * wmask
        s = measure.score(tf, idf_w, idx.doc_len[d], avg_dl)
        ok = jnp.all((tf > 0) | ~wmask) & jnp.any(wmask) if conjunctive \
            else jnp.any(tf * wmask > 0)
        return jnp.where(ok, s, -jnp.inf)

    scores = jax.lax.map(score_doc, jnp.arange(int(idx.n_docs), dtype=jnp.int32))
    s, d = jax.lax.top_k(scores, k)
    found = jnp.sum(s > -jnp.inf).astype(jnp.int32)
    return ranked.DRResult(jnp.where(s > -jnp.inf, d, -1).astype(jnp.int32),
                           s, found, jnp.int32(0))


@pytest.mark.parametrize("conjunctive", [True, False])
def test_drb_matches_bruteforce_tfidf(small_index, small_aux, tfidf, conjunctive):
    idx, model = small_index
    rng = np.random.default_rng(17)
    for trial in range(4):
        words = jnp.asarray(query_pool(idx, rng, 3), jnp.int32)
        wmask = jnp.ones(3, bool)
        bf = ranked.topk_bruteforce(idx, words, wmask, tfidf.idf(idx), k=10,
                                    conjunctive=conjunctive)
        if conjunctive:
            res = drb.topk_drb_and(idx, small_aux, words, wmask, tfidf, k=10)
        else:
            cap = int(np.asarray(idx.df)[np.asarray(words)].max()) + 2
            res = drb.topk_drb_or(idx, small_aux, words, wmask, tfidf, k=10,
                                  max_df_cap=cap)
        check_topk_equal(bf, res)


@pytest.mark.parametrize("conjunctive", [True, False])
def test_drb_bm25(small_index, small_aux, conjunctive):
    """Paper §5: DRB 'easily generalizes' to BM25 — verify it is exact."""
    idx, model = small_index
    bm25 = scoring.BM25()
    rng = np.random.default_rng(23)
    for trial in range(3):
        words = jnp.asarray(query_pool(idx, rng, 3), jnp.int32)
        wmask = jnp.ones(3, bool)
        bf = bruteforce_measure(idx, words, wmask, bm25, 10, conjunctive)
        if conjunctive:
            res = drb.topk_drb_and(idx, small_aux, words, wmask, bm25, k=10)
        else:
            cap = int(np.asarray(idx.df)[np.asarray(words)].max()) + 2
            res = drb.topk_drb_or(idx, small_aux, words, wmask, bm25, k=10,
                                  max_df_cap=cap)
        check_topk_equal(bf, res)


def test_bm25_requires_drb():
    with pytest.raises(ValueError):
        scoring.assert_dr_compatible(scoring.BM25())
    scoring.assert_dr_compatible(scoring.TfIdf())   # no raise


def test_drb_absent_word_empties_conjunction(small_index, small_aux, tfidf):
    idx, model = small_index
    df = np.asarray(idx.df)
    absent = int(np.flatnonzero(df == 0)[0]) if (df == 0).any() else None
    if absent is None:
        pytest.skip("corpus uses every vocabulary word")
    present = int(np.flatnonzero(df >= 3)[0])
    words = jnp.asarray([present, absent], jnp.int32)
    res = drb.topk_drb_and(idx, small_aux, words, jnp.ones(2, bool), tfidf, k=5)
    assert int(res.n_found) == 0


def test_drb_bitmap_semantics(small_index, small_aux, small_corpus):
    """1-runs in a word's bitmap equal its per-doc term frequencies."""
    idx, model = small_index
    rng = np.random.default_rng(31)
    ranks_by_doc = [model.rank_of_word[d] for d in small_corpus.doc_tokens]
    df = np.asarray(idx.df)
    w = int(rng.choice(np.flatnonzero((df >= 2) & (df <= 20))))
    # oracle: (doc, tf) pairs in doc order
    want = [(d, int((r == w).sum())) for d, r in enumerate(ranks_by_doc)
            if (r == w).any()]
    # from the bitmap: j-th 1 position and gap to the next
    occ = int(np.asarray(drb.word_occ(small_aux, jnp.int32(w))))
    got = []
    for j in range(1, len(want) + 1):
        i_j = int(drb.word_select1(small_aux, jnp.int32(w), jnp.int32(j)))
        i_next = int(drb.word_select1(small_aux, jnp.int32(w), jnp.int32(j + 1)))
        tf = (i_next if j < len(want) else occ) - i_j
        p = int(wtbc.locate(idx, jnp.int32(w), jnp.int32(i_j + 1)))
        d = int(wtbc.doc_of_pos(idx, jnp.int32(p)))
        got.append((d, tf))
    assert got == want


@pytest.mark.parametrize("measure", ["tfidf", "bm25"])
def test_drb_or_locate_kernel_matches_ref(small_index, small_aux, measure):
    """DRB/OR with its locates in the ``wavelet_locate`` kernel (TPU body
    under the interpreter) equals the jnp path bitwise and brute force;
    ``padded`` counts the dead (word, lane) lanes, ``Q * df_cap`` less the
    valid words' df."""
    from repro.kernels import backend

    idx, model = small_index
    m = scoring.TfIdf() if measure == "tfidf" else scoring.BM25()
    rng = np.random.default_rng(41)
    df = np.asarray(idx.df)
    has_bm = np.asarray(small_aux.has_bm)
    for trial in range(3):
        words = jnp.asarray(query_pool(idx, rng, 4), jnp.int32)
        wmask = jnp.asarray([True, True, True, trial != 1])
        cap = int(df[np.asarray(words)].max()) + 2
        ref_res = drb.topk_drb_or(idx, small_aux, words, wmask, m, k=10,
                                  max_df_cap=cap)
        with backend.force_plan("tpu:interpret"):
            kern = drb.topk_drb_or(idx, small_aux, words, wmask, m, k=10,
                                   max_df_cap=cap)
        for name in ("docs", "scores", "n_found", "padded"):
            np.testing.assert_array_equal(np.asarray(getattr(kern, name)),
                                          np.asarray(getattr(ref_res, name)),
                                          err_msg=name)
        valid = np.asarray(wmask) & has_bm[np.asarray(words)]
        assert int(kern.padded) == 4 * cap - int(df[np.asarray(words)][valid]
                                                 .sum())
        bf = bruteforce_measure(idx, words, wmask, m, 10, conjunctive=False)
        check_topk_equal(bf, kern)


def test_drb_or_padded_in_diagnostics(engine, query_batch):
    """The engine carries DRB/OR's dead lanes into ``diagnostics`` and the
    ``repro_engine_pad_lanes`` histogram."""
    import repro.obs as obs

    reg = obs.Registry(enabled=True)
    with obs.use(reg):
        res = engine.search(query_batch, mode="or", strategy="drb",
                            measure="bm25", k=5, df_cap=64)
    pad = res.diagnostics["padded"]
    df = np.asarray(engine.idx.df)
    ranks = np.asarray(engine.model.rank_of_word)[np.asarray(query_batch)]
    has_bm = np.asarray(engine.aux.has_bm)
    q = 1 << (ranks.shape[1] - 1).bit_length()     # the pow2 Q bucket
    want = [q * 64 - int(df[r][has_bm[r]].sum()) for r in ranks]
    np.testing.assert_array_equal(pad, want)
    h = reg.find("repro_engine_pad_lanes")[0]
    assert h.n == len(want) and h.total == float(sum(want))
