"""WTBC decode/count/locate vs direct token-array oracles."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core import wtbc
from repro.text import corpus


def flat_ranks(cp, model):
    flat = np.concatenate([np.concatenate([d, [0]]) for d in cp.doc_tokens])
    return model.rank_of_word[flat]


def test_decode_matches(small_index, small_corpus):
    idx, model = small_index
    ranks = flat_ranks(small_corpus, model)
    rng = np.random.default_rng(0)
    for p in rng.integers(0, len(ranks), 25):
        assert int(wtbc.decode_at(idx, jnp.int32(p))) == ranks[p]


def test_count_range_matches(small_index, small_corpus):
    idx, model = small_index
    ranks = flat_ranks(small_corpus, model)
    rng = np.random.default_rng(1)
    for _ in range(25):
        w = int(ranks[rng.integers(0, len(ranks))])
        lo = int(rng.integers(0, len(ranks)))
        hi = int(rng.integers(lo, len(ranks) + 1))
        got = int(wtbc.count_range(idx, jnp.int32(w), jnp.int32(lo), jnp.int32(hi)))
        assert got == int((ranks[lo:hi] == w).sum())


def test_locate_matches(small_index, small_corpus):
    idx, model = small_index
    ranks = flat_ranks(small_corpus, model)
    rng = np.random.default_rng(2)
    for _ in range(25):
        w = int(ranks[rng.integers(0, len(ranks))])
        occ = np.flatnonzero(ranks == w)
        j = int(rng.integers(1, len(occ) + 1))
        assert int(wtbc.locate(idx, jnp.int32(w), jnp.int32(j))) == occ[j - 1]


def test_full_decode_roundtrip(small_index, small_corpus):
    idx, model = small_index
    assert np.array_equal(wtbc.decode_all_np(idx, model),
                          flat_ranks(small_corpus, model))


def test_doc_geometry(small_index, small_corpus):
    idx, model = small_index
    lens = [len(d) for d in small_corpus.doc_tokens]
    starts = np.cumsum([0] + [l + 1 for l in lens[:-1]])
    for d in [0, 1, len(lens) // 2, len(lens) - 1]:
        lo, hi = wtbc.segment_extent(idx, jnp.int32(d), jnp.int32(d + 1))
        assert int(lo) == starts[d]
        # extent ends at the separator (hi = next doc start incl. the '$')
        mid = starts[d] + lens[d] // 2
        assert int(wtbc.doc_of_pos(idx, jnp.int32(mid))) == d


def test_extract_snippet(small_index, small_corpus):
    idx, model = small_index
    ranks = flat_ranks(small_corpus, model)
    got = np.asarray(wtbc.extract(idx, jnp.int32(37), 12))
    assert np.array_equal(got, ranks[37:49])


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(5, 40), st.integers(50, 400))
def test_build_properties_random_corpora(seed, n_docs, vocab):
    """Property sweep: whole-collection decode is the identity; df/occ agree
    with direct counting (drives corpus shape, skew, vocab)."""
    cp = corpus.make_corpus(n_docs=n_docs, mean_doc_len=20, vocab_size=vocab,
                            seed=seed % 10_000)
    idx, model = wtbc.build_index(cp.doc_tokens, cp.vocab_size, block=256)
    flat = np.concatenate([np.concatenate([d, [0]]) for d in cp.doc_tokens])
    ranks = model.rank_of_word[flat]
    assert np.array_equal(wtbc.decode_all_np(idx, model), ranks)
    occ = np.bincount(ranks, minlength=model.vocab_size)
    assert np.array_equal(np.asarray(idx.occ), occ.astype(np.int32))
    df = cp.doc_freqs()
    df_ranked = df[np.asarray(model.word_of_rank)]
    assert np.array_equal(np.asarray(idx.df), df_ranked.astype(np.int32))


@pytest.mark.parametrize("n_docs,mean_len,vocab,seed",
                         [(120, 60, 500, 3), (50, 200, 20_000, 7)])
def test_make_corpus_draws_what_choice_draws(n_docs, mean_len, vocab, seed):
    """The one-CDF sampler in make_corpus returns exactly the per-document
    ``rng.choice(..., p=zipf)`` draws it replaced (same generator stream)."""
    rng = np.random.default_rng(seed)
    lens = np.maximum(2, rng.lognormal(np.log(mean_len), 0.6,
                                       n_docs)).astype(np.int64)
    p = corpus.zipf_probs(vocab, 1.2)
    want = [rng.choice(np.arange(1, vocab), size=int(n), p=p) for n in lens]
    got = corpus.make_corpus(n_docs=n_docs, mean_doc_len=mean_len,
                             vocab_size=vocab, seed=seed).doc_tokens
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
        assert g.dtype == w.dtype
