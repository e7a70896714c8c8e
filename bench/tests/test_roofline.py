"""The descent's byte count reads the same work under every lowering."""
import numpy as np

from lib import roofline, traffic as traffic_lib
from conftest import traffic


def test_probe_arithmetic():
    assert roofline.probes(pops=3, cw_len_sum=4) == (3 + 1) * 2 * 4
    assert roofline.probe_bytes(32768) == 32772


def _bytes(coll, plan, queries, block):
    from repro.engine import EngineConfig, SearchEngine
    eng = SearchEngine.build(coll.doc_tokens(),
                             EngineConfig(block=block, kernel_backend=plan),
                             vocab_size=coll.vocab_size)
    res = eng.search(queries, mode="or", strategy="dr", measure="tfidf",
                     k=10)
    cw_len = np.asarray(eng.idx.cw_len)[np.asarray(eng.model.rank_of_word)]
    pops = np.asarray(res.pops)
    return [roofline.probes(p, cw_len[q].sum()) * roofline.probe_bytes(block)
            for p, q in zip(pops, queries)], pops


def test_bytes_equal_under_ref_and_tpu_plans(tiny_coll, tiny_config):
    qs = traffic_lib.Queries(traffic("dr_or"), tiny_coll, 5)
    queries = [next(qs) for _ in range(3)]
    ref, pops_ref = _bytes(tiny_coll, "ref", queries, tiny_config["block"])
    tpu, pops_tpu = _bytes(tiny_coll, "tpu:interpret", queries,
                           tiny_config["block"])
    assert np.array_equal(pops_ref, pops_tpu)
    assert ref == tpu and all(b > 0 for b in ref)
