"""The comparison passes the engine's answers and fails its control: the
reference computed in bfloat16, under the limits the cells' traffic files
set."""
import ml_dtypes
import numpy as np
import pytest

from lib import check, traffic as traffic_lib
from conftest import traffic

MIXES = ("dr_or", "drb_or_bm25")


@pytest.fixture(scope="module")
def engine(tiny_coll, tiny_config):
    from repro.engine import EngineConfig, SearchEngine
    return SearchEngine.build(tiny_coll.doc_tokens(),
                              EngineConfig(block=tiny_config["block"]),
                              vocab_size=tiny_coll.vocab_size)


def answers(engine, coll, mix, n=48):
    t = traffic(mix)
    qs = traffic_lib.Queries(t, coll, 11)
    queries = [next(qs) for _ in range(n)]
    res = engine.search(queries, **t["profile"])
    docs, scores = np.asarray(res.docs), np.asarray(res.scores)
    found = np.asarray(res.n_found)
    return t, [(q, docs[i, :found[i]], scores[i, :found[i]])
               for i, q in enumerate(queries)]


@pytest.mark.parametrize("mix", MIXES)
def test_engine_answers_are_correct(engine, tiny_coll, mix):
    t, rows = answers(engine, tiny_coll, mix)
    ok, table = check.verdict(check.numbers(tiny_coll, rows, t["profile"]),
                              t["limits"])
    assert ok, table


@pytest.mark.parametrize("mix", MIXES)
def test_bfloat16_control_is_not_correct(engine, tiny_coll, mix):
    t, rows = answers(engine, tiny_coll, mix)
    nums = check.numbers(tiny_coll, rows, t["profile"],
                         control_dtype=ml_dtypes.bfloat16)
    ok, table = check.verdict(nums, t["limits"])
    assert not ok, table


def test_wrong_answers_are_caught(engine, tiny_coll):
    t, rows = answers(engine, tiny_coll, "dr_or")
    i = next(i for i, (_, _, s) in enumerate(rows) if len(s) > 1
             and s[0] > s[-1])
    q, d, s = rows[i]
    rest = rows[:i] + rows[i + 1:]
    swapped = [(q, d[::-1].copy(), s)] + rest            # scores misplaced
    dropped = [(q, d[:-1], s[:-1])] + rest               # one hit missing
    for bad in (swapped, dropped):
        ok, table = check.verdict(check.numbers(tiny_coll, bad,
                                                t["profile"]), t["limits"])
        assert not ok, table
