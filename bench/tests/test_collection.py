"""The collection is fixed by the configuration up to the names of its
words, which the seed draws."""
import numpy as np

from lib import collection


def test_shapes_fixed_values_seeded(tiny_config):
    a = collection.make(tiny_config, 1)
    b = collection.make(tiny_config, 2**31 + 5)
    assert a.n_tokens == b.n_tokens == tiny_config["n_tokens"]
    assert a.n_docs == b.n_docs == tiny_config["n_docs"]
    assert np.array_equal(a.doc_len, b.doc_len)
    # the same frequency rank at every place, under other word ids
    assert np.array_equal(a.rank[a.tokens], b.rank[b.tokens])
    assert not np.array_equal(a.tokens, b.tokens)

    c = collection.make(tiny_config, 1)
    assert np.array_equal(a.tokens, c.tokens)


def test_index_counts_documents(tiny_coll):
    docs = tiny_coll.doc_tokens()
    df = np.zeros(tiny_coll.vocab_size, dtype=np.int64)
    for d in docs:
        df[np.unique(d)] += 1
    assert np.array_equal(df, tiny_coll.df)
    w = int(np.argmax(df))
    want = np.concatenate([np.full(int(np.sum(d == w)), i)
                           for i, d in enumerate(docs)])
    assert np.array_equal(tiny_coll.postings(w), want)


def test_window_same_work_every_seed(tiny_config):
    """Two seeds send the same words, by rank, under other ids."""
    from conftest import traffic
    from lib import traffic as traffic_lib
    got = []
    for seed in (3, 2**31 + 11):
        coll = collection.make(tiny_config, seed)
        coll.index()
        qs = traffic_lib.Queries(traffic("dr_or"), coll, seed)
        win = qs.window(50)
        got.append(([tuple(coll.rank[q].tolist()) for q in win], win))
    assert got[0][0] == got[1][0]
    assert got[0][1] != got[1][1]
