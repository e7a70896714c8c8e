"""repro.obs — observability subsystem contract tests (DESIGN.md §10).

Pins, in order of importance:

* **bitwise neutrality** — enabling the registry must not change any ranked
  answer (observation happens on host copies after device values exist);
* **disabled is free** — with the registry and the span recorder off, no
  span is allocated, no clock is read for one, no observation lands, and a
  recording call is a cheap checked no-op;
* **spans** — a served batch records the span tree of DESIGN.md §10 (each
  child inside its parent, ids linking requests to their batch), the ring
  keeps the newest spans and counts the dropped ones;
* **histogram exactness** — percentile reconstruction is exact for integer
  observations below 2*SUBBUCKETS and within 1/SUBBUCKETS relative error
  elsewhere; p0/p100 are the tracked exact extremes;
* **diagnostics threading** — DRResult.padded/overflowed reach
  SearchResults -> RowResult -> server stats/registry on the plain, mega,
  and sharded paths;
* **stats under concurrency** — SearchServer.stats is safe to hammer while
  traffic flows and never blends two engines across swap_engine.
"""
import json
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

import repro.obs as obs
from repro.engine import EngineConfig, SearchEngine
from repro.obs.metrics import SUBBUCKETS, bucket_hi, bucket_lo
from repro.obs import tracing
from repro.serve import QueryProfile, SearchServer, loadgen
from repro.serve.server import RowResult, _slice_rows
from repro.text import corpus


@pytest.fixture(scope="module")
def obs_corpus():
    return corpus.make_corpus(n_docs=100, mean_doc_len=50, vocab_size=400,
                              seed=21)


@pytest.fixture(scope="module")
def obs_engine(obs_corpus):
    return SearchEngine.build(obs_corpus, EngineConfig(block=512))


@pytest.fixture(scope="module")
def obs_queries(obs_engine):
    return loadgen.sample_queries(obs_engine, 16, 3, seed=5)


@pytest.fixture
def spans_on():
    """The process span recorder on for one test, emptied around it."""
    obs.SPANS.drain()
    obs.enable_spans()
    try:
        yield obs.SPANS
    finally:
        obs.enable_spans(False)
        obs.SPANS.drain()


# ---------------------------------------------------------------------------
# metrics: histogram exactness + primitives
# ---------------------------------------------------------------------------

def test_histogram_exact_for_small_integers():
    """Integer observations < 2*SUBBUCKETS live in width-<=1 buckets, so
    nearest-rank reconstruction equals numpy's inverted_cdf exactly — the
    'exact p50/p95/p99' claim for work counters and batch sizes."""
    rng = np.random.default_rng(0)
    reg = obs.Registry(enabled=True)
    h = reg.histogram("work")
    vals = rng.integers(1, 2 * SUBBUCKETS, size=2000)
    h.observe_many(vals.tolist())
    for q in (1, 25, 50, 75, 95, 99):
        want = float(np.percentile(vals, q, method="inverted_cdf"))
        assert h.quantile(q) == want, q


def test_histogram_relative_error_bound():
    rng = np.random.default_rng(1)
    reg = obs.Registry(enabled=True)
    h = reg.histogram("lat")
    vals = rng.lognormal(mean=-5.0, sigma=2.0, size=5000)
    h.observe_many(vals.tolist())
    for q in (50, 90, 95, 99):
        want = float(np.percentile(vals, q, method="inverted_cdf"))
        got = h.quantile(q)
        assert got <= want                        # bucket LOWER bound
        assert (want - got) / want <= 1.0 / SUBBUCKETS + 1e-12, q


def test_histogram_extremes_zeros_and_buckets():
    reg = obs.Registry(enabled=True)
    h = reg.histogram("h")
    h.observe_many([0.0, 0.0, 0.25, 3.0, 1000.0])
    assert h.quantile(0) == 0.0 and h.quantile(100) == 1000.0   # exact min/max
    assert h.quantile(30) == 0.0                  # zeros bucket
    assert h.n == 5 and h.n_zero == 2
    assert h.mean == pytest.approx((0.25 + 3.0 + 1000.0) / 5)
    # bucket geometry: lo/hi bracket every value, width = 2^e / SUBBUCKETS
    for v in (0.25, 3.0, 1000.0, 1e-9, 7.99):
        from repro.obs.metrics import bucket_index
        i = bucket_index(v)
        assert bucket_lo(i) <= v < bucket_hi(i), v


def test_registry_disabled_records_nothing():
    reg = obs.Registry(enabled=False)
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc(5), g.set(3.0), h.observe(1.0)
    assert c.value == 0 and g.value == 0.0 and h.n == 0
    reg.enabled = True
    c.inc(5), g.set(3.0), h.observe(1.0)
    assert c.value == 5 and g.value == 3.0 and h.n == 1


def test_registry_get_or_create_and_kind_guard():
    reg = obs.Registry(enabled=True)
    assert reg.counter("x", {"a": "1"}) is reg.counter("x", {"a": "1"})
    assert reg.counter("x", {"a": "1"}) is not reg.counter("x", {"a": "2"})
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x", {"a": "1"})


def test_default_registry_enable_and_use():
    assert obs.default_registry().enabled is False     # process default: off
    mine = obs.Registry(enabled=True)
    with obs.use(mine):
        assert obs.default_registry() is mine
        obs.default_registry().counter("k").inc()
    assert obs.default_registry() is not mine
    assert mine.counter("k").value == 1


def test_disabled_recording_is_cheap():
    """The disabled path is one attr load + branch — pin a generous ceiling
    so a lock/allocation sneaking in fails loudly (DESIGN.md §10 budget)."""
    reg = obs.Registry(enabled=False)
    c, h = reg.counter("c"), reg.histogram("h")
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        c.inc()
        h.observe(1.0)
    per_call_us = (time.perf_counter() - t0) / (2 * n) * 1e6
    assert per_call_us < 5.0, f"{per_call_us:.2f}us per disabled record"


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_timeline_spans_and_stage_durations():
    """The recorder itself: spans opened on one thread nest (parent id,
    inherited ref id), explicit times are kept, attrs merge, a close
    unwinds inner spans an exception left open, and ``add`` records a
    finished span with the ids given."""
    rec = tracing.SpanRecorder(capacity=64)
    outer = rec.open("serve.batch", ref_id=7, start_ns=1_000,
                     attrs={"n_real": 2})
    inner = rec.open("engine.launch", start_ns=1_100)
    rec.tag(executor="dr_or")                 # innermost open span
    assert rec.close(inner, end_ns=1_400, compiled=True) == 1_400
    left_open = rec.open("serve.fetch", start_ns=1_500)
    rec.close(outer, end_ns=2_000, requests=[3, 4])
    req = rec.add("serve.request", 500, 2_100, ref_id=3)
    rec.add("serve.queue", 500, 1_000, parent_id=req, ref_id=3,
            attrs={"batch": 7})
    log = rec.drain()
    assert log.dropped == 0
    by = {sp.name: sp for sp in log.spans}
    assert set(by) == {"engine.launch", "serve.batch", "serve.request",
                       "serve.queue"}               # serve.fetch unwound
    launch, batch = by["engine.launch"], by["serve.batch"]
    assert (launch.start_ns, launch.end_ns, launch.duration_ns) == \
        (1_100, 1_400, 300)
    assert launch.parent_id == batch.span_id == outer.span_id
    assert launch.ref_id == batch.ref_id == 7
    assert launch.attrs == {"executor": "dr_or", "compiled": True}
    assert batch.parent_id == 0
    assert batch.attrs == {"n_real": 2, "requests": [3, 4]}
    assert left_open.span_id not in {sp.span_id for sp in log.spans}
    assert by["serve.queue"].parent_id == by["serve.request"].span_id == req
    assert by["serve.queue"].attrs == {"batch": 7}
    # ids are unique across spans, requests and batches
    ids = [sp.span_id for sp in log.spans]
    assert len(set(ids)) == len(ids) and rec.new_id() not in ids
    assert rec.drain() == tracing.SpanLog([], 0)


def test_span_ring_drops_oldest_and_counts():
    rec = tracing.SpanRecorder(capacity=4)
    for i in range(6):
        rec.add(f"s{i}", i, i + 1)
    log = rec.drain()
    assert [sp.name for sp in log.spans] == ["s2", "s3", "s4", "s5"]
    assert log.dropped == 2
    rec.add("s6", 6, 7)                 # drained: the count starts over
    log = rec.drain()
    assert [sp.name for sp in log.spans] == ["s6"] and log.dropped == 0
    with pytest.raises(ValueError):
        tracing.SpanRecorder(capacity=0)


def test_span_ring_under_concurrent_adds():
    """Submitter threads and the dispatch thread add to one ring: no span
    is lost uncounted, and each thread's open spans stay its own."""
    import sys
    rec = tracing.SpanRecorder(capacity=500)
    n_threads, n_each = 16, 400
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for j in range(n_each):
                sp = rec.open(f"t{i}")
                rec.add("leaf", j, j + 1)
                rec.close(sp)
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
    log = rec.drain()
    assert len(log.spans) == 500
    assert len(log.spans) + log.dropped == 2 * n_threads * n_each
    opened = [sp for sp in log.spans if sp.name != "leaf"]
    assert all(sp.parent_id == 0 for sp in opened)    # no cross-thread nest
    assert len({sp.span_id for sp in log.spans}) == 500


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _filled_registry() -> obs.Registry:
    reg = obs.Registry(enabled=True)
    reg.counter("repro_c_total", {"x": "1"}, "a counter").inc(3)
    reg.gauge("repro_g", None, "a gauge").set(2.5)
    h = reg.histogram("repro_h_seconds", {"stage": "s"}, "a histogram")
    h.observe_many([0.0, 0.001, 0.002, 0.5, 3.0])
    return reg


def test_prometheus_rendering_parses_and_is_cumulative():
    text = obs.render_prometheus(_filled_registry())
    lines = [l for l in text.splitlines() if l and not l.startswith("#")]
    assert 'repro_c_total{x="1"} 3' in lines
    assert "repro_g 2.5" in lines
    buckets = []
    for l in lines:
        if l.startswith("repro_h_seconds_bucket"):
            le = l.split('le="')[1].split('"')[0]
            buckets.append((float("inf") if le == "+Inf" else float(le),
                            int(l.rsplit(" ", 1)[1])))
    assert buckets == sorted(buckets)          # le ascending, counts cumulative
    assert buckets[-1] == (float("inf"), 5)
    assert [c for _, c in buckets] == sorted(c for _, c in buckets)
    assert "repro_h_seconds_count" in text and "repro_h_seconds_sum" in text
    # every sample line parses as "name{labels} value"
    for l in lines:
        name_part, val = l.rsplit(" ", 1)
        float(val)
        assert name_part.startswith("repro_")


def test_jsonl_snapshot_roundtrip(tmp_path):
    reg = _filled_registry()
    line = obs.snapshot_line(reg)
    d = json.loads(line)
    assert d["metrics"]['repro_c_total{x="1"}'] == 3
    assert d["metrics"]['repro_h_seconds{stage="s"}']["count"] == 5
    p = tmp_path / "m.jsonl"
    obs.write_jsonl(p, reg)
    obs.write_jsonl(p, reg)
    assert len(p.read_text().splitlines()) == 2
    snap = obs.dump(reg, p)
    assert snap == reg.snapshot()
    assert len(p.read_text().splitlines()) == 3


def test_metrics_http_server_scrape():
    reg = _filled_registry()
    with obs.MetricsServer(reg, port=0) as srv:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10).read().decode()
        assert 'repro_c_total{x="1"} 3' in body
        j = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics.json", timeout=10).read())
        assert j["metrics"]["repro_g"] == 2.5
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)


# ---------------------------------------------------------------------------
# serving integration: spans, stage histograms, neutrality, overhead
# ---------------------------------------------------------------------------

def _dummy_engine(delay_s: float = 0.0, padded=None):
    def search(queries, **kw):
        if delay_s:
            time.sleep(delay_s)
        B = len(queries)
        k = kw.get("k") or 3
        ns = types.SimpleNamespace(
            docs=np.tile(np.arange(k, dtype=np.int32), (B, 1)),
            scores=np.zeros((B, k), np.float32),
            n_found=np.full(B, k, np.int32), work=np.ones(B, np.int32),
            pops=None, overflowed=None, match_pos=None, match_len=None,
            k=k, mode=kw.get("mode", "and"), strategy="dr", measure="tfidf")
        if padded is not None:
            ns.padded = np.full(B, padded, np.int32)
        return ns
    return types.SimpleNamespace(
        search=search, model=types.SimpleNamespace(vocab_size=100),
        stats={"executors": 0, "traces": {}},
        warmup=lambda *a, **kw: 0)


BATCH_CHILDREN = ("engine.prepare", "engine.launch", "engine.wait",
                  "serve.fetch", "serve.complete")


def _batches(spans):
    """``[(batch span, its children in start order)]`` in start order."""
    kids = {}
    for sp in spans:
        kids.setdefault(sp.parent_id, []).append(sp)
    return sorted(((sp, sorted(kids.get(sp.span_id, []),
                               key=lambda c: c.start_ns))
                   for sp in spans if sp.name == "serve.batch"),
                  key=lambda bk: bk[0].start_ns)


def test_server_spans_and_stage_histograms_with_registry(spans_on):
    reg = obs.Registry(enabled=True)
    eng = _dummy_engine(delay_s=0.002)
    with SearchServer(eng, max_batch=4, max_wait_ms=5.0, cache_size=16,
                      registry=reg) as server:
        tickets = [server.submit([1 + i % 7]) for i in range(12)]
        rows = [t.result(timeout=10.0) for t in tickets]
        hit = server.submit([1])               # replay -> cache-hit span
        hit.result(timeout=10.0)
    assert all(r.n_found == 3 for r in rows)
    log = spans_on.drain()
    assert log.dropped == 0
    # every dispatched ticket has its request span and its queue span, the
    # queue span naming the batch that took it
    req = {sp.ref_id: sp for sp in log.spans if sp.name == "serve.request"}
    queue = {sp.ref_id: sp for sp in log.spans if sp.name == "serve.queue"}
    batches = {sp.ref_id: sp for sp in log.spans if sp.name == "serve.batch"}
    assert len(req) == 13 and len(queue) == 12 and hit.request_id in req
    assert hit.cache_hit and hit.request_id not in queue
    assert len(batches) == server.stats["dispatches"]
    assert sum(b.attrs["n_real"] for b in batches.values()) == 12
    for t in tickets:
        r, q = req[t.request_id], queue[t.request_id]
        assert q.parent_id == r.span_id
        assert r.start_ns == q.start_ns <= q.end_ns <= r.end_ns
        assert abs(q.end_ns - t.t_dispatch * 1e9) < 1e3
        assert t.request_id in batches[q.attrs["batch"]].attrs["requests"]
    # the ticket's decomposition is exact: queue_wait + service == latency
    for t in tickets:
        assert t.queue_wait_s + t.service_s == pytest.approx(t.latency_s)
    # registry: stage histograms fed from the spans, counters agree
    by_stage = {dict(h.labels)["stage"]: h
                for h in reg.find("repro_request_stage_seconds")}
    assert by_stage["device"].n == 12
    assert by_stage["total"].n == 13           # cache hit records total too
    assert by_stage["queue_wait"].n == 12
    assert by_stage["slice"].n == 12
    assert by_stage["device"].quantile(0) >= 0.002    # the engine's sleep
    served = [c for c in reg.find("repro_server_requests_total")
              if dict(c.labels)["outcome"] == "served"][0]
    assert served.value == 13 == server.stats["served"]
    hits = reg.find("repro_cache_hits_total")[0]
    assert hits.value == 1 == server.stats["cache"]["hits"]
    assert reg.find("repro_batch_size")        # per-lane batch histogram
    assert reg.find("repro_dispatch_seconds")[0].n == \
        server.stats["dispatches"]


def test_server_disabled_registry_allocates_nothing():
    eng = _dummy_engine()
    reg = obs.Registry(enabled=False)
    assert not obs.SPANS.enabled
    obs.SPANS.drain()
    with SearchServer(eng, max_batch=4, cache_size=0,
                      registry=reg) as server:
        t = server.submit([3])
        t.result(timeout=10.0)
    assert t.request_id == 0                   # no span when off
    assert obs.SPANS.drain() == tracing.SpanLog([], 0)
    for m in reg.metrics():
        v = m._snapshot()
        assert (v == 0 or v == 0.0 or
                (isinstance(v, dict) and v["count"] == 0)), m.name


def test_spans_off_served_batch_allocates_no_span_nor_reads_the_clock(
        monkeypatch, obs_engine, obs_queries):
    """With the recorder off a served batch (dummy and real engine) never
    builds a span, an open span or an id, and never calls monotonic_ns."""
    calls = []

    def forbidden(*a, **kw):
        calls.append(a)
        raise AssertionError("span machinery touched while off")

    ns = time.monotonic_ns
    monkeypatch.setattr(time, "monotonic_ns",
                        lambda: calls.append("clock") or ns())
    for name in ("Span", "_Open"):
        monkeypatch.setattr(tracing, name, forbidden)
    monkeypatch.setattr(tracing.SpanRecorder, "new_id", forbidden)
    profile = QueryProfile(mode="or", strategy="dr", k=5)
    try:
        with SearchServer(_dummy_engine(), max_batch=4,
                          cache_size=4) as server:
            for q in ([3], [4], [3]):          # the last one a cache hit
                server.submit(q).result(timeout=10.0)
        with SearchServer(obs_engine, max_batch=2, max_wait_ms=20.0,
                          cache_size=0) as server:
            tickets = [server.submit(q, profile) for q in obs_queries[:2]]
            for t in tickets:
                t.result(timeout=60.0)
    finally:
        obs_engine.obs_registry = None
    assert calls == []
    assert all(t.request_id == 0 for t in tickets)
    assert obs.SPANS.drain() == tracing.SpanLog([], 0)


def test_spans_of_a_served_batch(spans_on, obs_engine, obs_queries):
    """Spans on: each served batch is ``serve.wait`` then ``serve.batch``
    with its five children in order, each inside its parent; request ids
    link the requests to their batch; ``engine.launch`` carries
    ``compiled`` on the first call of an executor only."""
    profile = QueryProfile(mode="or", strategy="dr", k=7)   # a fresh executor
    q = obs_queries[0]
    try:
        with SearchServer(obs_engine, max_batch=4, max_wait_ms=1.0,
                          cache_size=0) as server:
            tickets = []
            for _ in range(2):                 # two batches, one executor
                tickets.append(server.submit(q, profile))
                tickets[-1].result(timeout=120.0)
    finally:
        obs_engine.obs_registry = None
    log = spans_on.drain()
    batches = _batches(log.spans)
    assert len(batches) == 2
    waits = {sp.ref_id: sp for sp in log.spans if sp.name == "serve.wait"}
    compiled, ends = [], []
    for batch, kids in batches:
        assert batch.parent_id == 0
        assert [k.name for k in kids] == list(BATCH_CHILDREN)
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns              # one after another
        for k in kids:
            assert batch.start_ns <= k.start_ns <= k.end_ns <= batch.end_ns
            assert k.ref_id == batch.ref_id
        assert kids[-1].end_ns == batch.end_ns
        assert waits[batch.ref_id].end_ns == batch.start_ns
        if ends:                               # the thread's time is tiled
            assert waits[batch.ref_id].start_ns == ends[-1]
        ends.append(batch.end_ns)
        assert batch.attrs["n_real"] == 1 and batch.attrs["padded"] == 0
        assert batch.attrs["executor"] == "dr_or" == \
            kids[1].attrs["executor"]
        compiled.append(kids[1].attrs["compiled"])
        (rid,) = batch.attrs["requests"]
        req = [sp for sp in log.spans
               if sp.name == "serve.request" and sp.ref_id == rid]
        queue = [sp for sp in log.spans
                 if sp.name == "serve.queue" and sp.ref_id == rid]
        assert len(req) == len(queue) == 1
        assert queue[0].parent_id == req[0].span_id
        assert queue[0].attrs == {"batch": batch.ref_id}
        assert queue[0].end_ns == batch.start_ns
        assert req[0].start_ns <= batch.start_ns <= batch.end_ns
    assert compiled == [True, False]
    assert [t.request_id for t in tickets] == \
        [b.attrs["requests"][0] for b, _ in batches]


def test_executors_are_named_after_their_path(obs_engine, obs_queries):
    """A profile's ``XLA Modules`` line names the program: each executor's
    jitted function is named after its path (``jit_<name>``)."""
    from repro.engine import executors
    qs = obs_queries[:2]
    for kw, name in (
            (dict(mode="or", strategy="dr"), "dr_or"),
            (dict(mode="and", strategy="dr"), "dr_and"),
            (dict(mode="or", strategy="dr", mega=True), "dr_or_mega"),
            (dict(mode="or", strategy="drb", measure="bm25"), "drb_or"),
            (dict(mode="and", strategy="drb"), "drb_and"),
            (dict(mode="phrase"), "positional_phrase"),
            (dict(mode="near", window=4), "positional_near")):
        text = obs_engine.lower(qs, k=5, **kw).as_text()
        assert text.startswith(f"module @jit_{name} "), (name, text[:80])
    key = executors.ExecutorKey("sharded", "dr", "or", None, 5, (2, 2),
                                None, None, 1)
    assert executors.name_of(key) == "sharded_dr_or"


def test_instrumentation_is_bitwise_neutral(obs_engine, obs_queries):
    """Identical queries with the registry and the span recorder off, the
    registry on, and both on: every ranked leaf is bitwise equal —
    observation reads results, it never feeds back."""
    kw = dict(k=6, mode="or", strategy="dr")
    base = obs_engine.search(obs_queries[:4], **kw)
    reg = obs.Registry(enabled=True)
    with obs.use(reg):
        inst = obs_engine.search(obs_queries[:4], **kw)
        obs.enable_spans()
        try:
            traced = obs_engine.search(obs_queries[:4], **kw)
        finally:
            obs.enable_spans(False)
    assert reg.find("repro_engine_searches_total")     # it DID record
    names = [sp.name for sp in obs.SPANS.drain().spans]
    assert names == ["engine.prepare", "engine.launch", "engine.record"]
    for name in ("docs", "scores", "n_found", "work", "pops"):
        for other in (inst, traced):
            np.testing.assert_array_equal(np.asarray(getattr(base, name)),
                                          np.asarray(getattr(other, name)),
                                          err_msg=name)


def test_engine_records_work_and_roofline(obs_engine, obs_queries):
    """The engine's work histograms and counters; no roofline gauge is
    exported any more (modelled bytes over host latency, read by
    nothing)."""
    reg = obs.Registry(enabled=True)
    with obs.use(reg):
        res = obs_engine.search(obs_queries[:3], k=5, mode="or",
                                strategy="dr")
    pops_h = reg.find("repro_engine_pops")[0]
    assert pops_h.n == 3
    assert pops_h.total == float(np.asarray(res.pops).sum())
    assert reg.find("repro_engine_trips")[0].n == 3
    assert reg.find("repro_engine_dispatch_seconds")[0].n == 1
    rows = [c for c in reg.find("repro_engine_rows_total")][0]
    assert rows.value == 3
    assert not reg.find("repro_roofline_achieved_frac")


# ---------------------------------------------------------------------------
# satellite 3: diagnostics threading (padded/overflowed end to end)
# ---------------------------------------------------------------------------

def test_slice_rows_threads_padded_per_row():
    res = types.SimpleNamespace(
        docs=np.zeros((3, 2), np.int32), scores=np.zeros((3, 2), np.float32),
        n_found=np.ones(3, np.int32), work=np.ones(3, np.int32),
        pops=np.array([4, 5, 6]), overflowed=np.array([False, True, False]),
        padded=np.array([0, 2, 7]), match_pos=None, match_len=None,
        k=2, mode="or", strategy="dr", measure="tfidf")
    rows = _slice_rows(res, 2)                 # pad row 2 dropped
    assert [r.padded for r in rows] == [0, 2]
    assert [r.overflowed for r in rows] == [False, True]
    assert [r.pops for r in rows] == [4, 5]
    # engines that report no padded diagnostics (dummy/legacy) -> None
    del res.padded
    assert all(r.padded is None for r in _slice_rows(res, 2))


def test_padded_threads_engine_to_server_stats(obs_engine, obs_queries):
    """DR beam search reports pad-waste; it must reach RowResult, the
    server's stats dict, and the registry counter un-mangled."""
    res = obs_engine.search(obs_queries[:2], k=5, mode="or", strategy="dr",
                            beam_width=4)
    assert res.padded is not None
    want = int(np.asarray(res.padded).sum())
    reg = obs.Registry(enabled=True)
    profile = QueryProfile(mode="or", strategy="dr", k=5, beam_width=4)
    with SearchServer(obs_engine, max_batch=2, max_wait_ms=50.0,
                      cache_size=0, registry=reg) as server:
        t0 = server.submit(obs_queries[0], profile)
        t1 = server.submit(obs_queries[1], profile)
        rows = [t0.result(timeout=60.0), t1.result(timeout=60.0)]
    got = [r.padded for r in rows]
    assert all(p is not None for p in got)
    # batched serving may batch the two rows together or not; either way the
    # per-row diagnostic sums match the direct batched search
    if server.stats["batch_hist"] == {2: 1}:
        assert got == [int(p) for p in np.asarray(res.padded)]
        assert server.stats["padded"] == want
    assert server.stats["padded"] == sum(got)
    assert reg.find("repro_server_padded_lanes_total")[0].value == sum(got)
    obs_engine.obs_registry = None             # unpin the module fixture


def test_diagnostics_thread_mega_path(obs_engine, obs_queries):
    """The pool-frontier megabatch core pops exactly one segment per live
    row per trip — zero pad lanes by construction — so ``padded`` is None
    end to end, while pops/overflowed still thread per row."""
    res = obs_engine.search(obs_queries[:3], k=5, mode="or", strategy="dr",
                            mega=True)
    assert res.padded is None and res.overflowed is not None
    assert res.pops is not None
    rows = _slice_rows(res, 3)
    assert all(r.padded is None for r in rows)
    assert [r.pops for r in rows] == [int(p) for p in np.asarray(res.pops)]
    assert [r.overflowed for r in rows] == \
        [bool(o) for o in np.asarray(res.overflowed)]
    # contrast: the lockstep beam path DOES report pad waste
    lock = obs_engine.search(obs_queries[:3], k=5, mode="or", strategy="dr",
                             beam_width=4)
    assert lock.padded is not None


@pytest.mark.slow
def test_padded_threads_sharded_path(obs_corpus):
    """n_shards=1 on the single CPU device: the sharded merge must psum and
    return padded for every method, DRB/OR's dead lanes included."""
    eng = SearchEngine.shard(obs_corpus, n_shards=1,
                             config=EngineConfig(block=512))
    qs = loadgen.sample_queries(eng, 4, 2, seed=5)
    res = eng.search(qs, k=5, mode="or", strategy="dr", beam_width=2)
    assert res.padded is not None
    assert np.asarray(res.padded).shape == (4,)
    single = SearchEngine.build(obs_corpus, EngineConfig(block=512))
    sres = single.search(qs, k=5, mode="or", strategy="dr", beam_width=2)
    np.testing.assert_array_equal(np.asarray(res.padded),
                                  np.asarray(sres.padded))
    rows = _slice_rows(res, 4)
    assert all(r.padded is not None for r in rows)
    drb_kw = dict(k=5, mode="or", strategy="drb", measure="bm25")
    np.testing.assert_array_equal(
        [r.padded for r in _slice_rows(eng.search(qs, **drb_kw), 4)],
        np.asarray(single.search(qs, **drb_kw).padded))


# ---------------------------------------------------------------------------
# satellite 1: stats under concurrency / across swap
# ---------------------------------------------------------------------------

def test_stats_safe_under_concurrent_traffic():
    eng = _dummy_engine(delay_s=0.001)
    errors = []
    with SearchServer(eng, max_batch=4, max_wait_ms=1.0, cache_size=8,
                      queue_depth=128) as server:
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                try:
                    st = server.stats
                    assert st["served"] <= st["submitted"]
                    assert set(st["cache"]) == {"hits", "misses", "hit_rate",
                                                "size", "capacity"}
                except Exception as e:          # pragma: no cover
                    errors.append(e)
                    return
        readers = [threading.Thread(target=reader) for _ in range(3)]
        for r in readers:
            r.start()
        tickets = [server.submit([1 + i % 9]) for i in range(60)]
        for t in tickets:
            t.result(timeout=10.0)
        stop.set()
        for r in readers:
            r.join()
    assert not errors
    assert server.stats["served"] == 60


def test_stats_never_blend_engines_across_swap():
    eng_a = _dummy_engine()
    eng_a.stats = {"executors": 1, "traces": {"a": 1}}
    eng_a.content_tag = 0xA
    eng_b = _dummy_engine()
    eng_b.stats = {"executors": 7, "traces": {"b": 3}}
    eng_b.content_tag = 0xB
    with SearchServer(eng_a, max_batch=2, cache_size=4) as server:
        server.submit([1]).result(timeout=10.0)
        st = server.stats
        assert (st["executors"], st["traces"], st["engine_tag"]) == (1, 1, 0xA)
        server.swap_engine(eng_b)
        st = server.stats
        assert (st["executors"], st["traces"], st["engine_tag"]) == (7, 3, 0xB)
        assert st["swaps"] == 1
        server.submit([1]).result(timeout=10.0)     # still serves post-swap
    assert server.stats["served"] == 2


# ---------------------------------------------------------------------------
# loadgen: queue/service split (satellite 2)
# ---------------------------------------------------------------------------

def test_loadreport_splits_queue_and_service():
    eng = _dummy_engine(delay_s=0.005)
    with SearchServer(eng, max_batch=4, max_wait_ms=1.0,
                      cache_size=0) as server:
        rep = loadgen.closed_loop(server, [[1 + i % 9] for i in range(24)],
                                  n_workers=6)
    assert rep.n_ok == 24
    assert len(rep.queue_ms) == 24 and len(rep.service_ms) == 24
    for p in ("queue_p50_ms", "queue_p99_ms", "service_p50_ms",
              "service_p99_ms"):
        assert np.isfinite(getattr(rep, p)), p
    # service includes the 5ms engine sleep; queue wait is bounded by the
    # 1ms coalescing budget plus backlog
    assert rep.service_p50_ms >= 5.0
    assert "queue p50" in rep.summary() and "service p50" in rep.summary()
    # the decomposition is exact in aggregate: sum(total) == sum(q) + sum(s)
    assert rep.latencies_ms.sum() == pytest.approx(
        rep.queue_ms.sum() + rep.service_ms.sum(), rel=1e-9)
    assert rep.stages is None                  # registry off -> no breakdown


def test_loadreport_stage_breakdown_with_registry(spans_on):
    reg = obs.Registry(enabled=True)
    eng = _dummy_engine(delay_s=0.002)
    with SearchServer(eng, max_batch=4, max_wait_ms=1.0, cache_size=0,
                      registry=reg) as server:
        rep = loadgen.open_loop(server, [[1 + i % 9] for i in range(20)],
                                target_qps=400.0, timeout_s=30.0)
    assert rep.n_ok == 20
    assert rep.stages is not None
    for s in ("queue_wait", "device", "slice", "total"):
        assert s in rep.stages
        assert rep.stages[s]["count"] > 0
        assert np.isfinite(rep.stages[s]["p99_ms"])
    assert rep.stages["total"]["count"] == 20
