"""A whole run, with the look for a chip skipped and the timed path broken
underneath, comes out not correct."""
import dataclasses
import json
import time

import pytest

import run as bench_run
from conftest import traffic

SPEC = {"end_to_end": [{"name": "p95_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def run_tiny(tiny_config, mix, seconds=2.0):
    t = traffic(mix)
    if t["arrivals"]["loop"] == "open":
        t["arrivals"] = {"loop": "open", "rate_qps": 20.0}
    else:
        t["arrivals"] = {"loop": "closed", "clients": 4}
    cell = {"name": f"tiny.{mix}", "config": "tiny", "traffic": mix,
            "chips": 1}
    return bench_run.run(cell["name"], 987654321987, seconds, False,
                         spec=SPEC, cell=cell, config=tiny_config, traffic=t,
                         require_tpu=False, t_start=time.monotonic())


def _altered(search):
    """An answer altered where it is produced: every batch's first row
    scores 1e-4 higher."""
    def broken(self, queries, **kw):
        res = search(self, queries, **kw)
        return dataclasses.replace(res,
                                   scores=res.scores.at[0].multiply(1.0001))
    return broken


def _half_batch(search):
    """Half of the batch left out: rows past the first half get the
    answer of row 0."""
    def broken(self, queries, **kw):
        res = search(self, queries, **kw)
        h = max(1, (len(queries) + 1) // 2)
        fix = lambda a: a.at[h:].set(a[0])            # noqa: E731
        return dataclasses.replace(res, docs=fix(res.docs),
                                   scores=fix(res.scores),
                                   n_found=fix(res.n_found))
    return broken


def test_sound_run_is_correct(tiny_config):
    out = run_tiny(tiny_config, "dr_or")
    assert out["correct"], out["checks"]
    json.dumps(out)


@pytest.mark.parametrize("fault", [_altered, _half_batch])
@pytest.mark.parametrize("mix", ["dr_or", "drb_or_bm25"])
def test_broken_timed_path_is_not_correct(tiny_config, monkeypatch, fault,
                                          mix):
    from repro.engine import facade
    monkeypatch.setattr(facade.SearchEngine, "search",
                        fault(facade.SearchEngine.search))
    out = run_tiny(tiny_config, mix)
    assert not out["correct"], out["checks"]
