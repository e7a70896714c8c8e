"""Table 6 (beyond-paper): online serving — micro-batching, caching, and
latency percentiles.

The paper's timing tables measure isolated queries; a server sees
*concurrent* traffic, and its numbers are distributional: sustained
throughput, p50/p95/p99 latency, batch-size mix, cache hit rate.  Four
passes over the shared benchmark engine:

  1. closed loop, micro-batcher ON  (max_batch=B, no cache)
  2. closed loop, one-query-at-a-time (max_batch=1, no cache) — the baseline
     the batcher must beat at equal client concurrency
  3. open loop at a fixed offered QPS (no cache) — latency under load
  4. closed loop over a Zipf-repeated workload with the cache ON
  5. open loop with the obs registry and span recorder ENABLED on a DR
     profile: per-stage latency attribution (queue_wait/device/slice/total)
     fed from the spans, straight from the registry (DESIGN.md §10)

The workload is drawn from the selective band (low df, 2 words): the
interactive regime where per-call host overhead dominates and coalescing
pays.  Every pass runs after ``server.warmup`` and asserts the executor
trace counter stayed flat — serving must never compile on the query path.
Every report also carries the queue-wait/service percentile split, so a
regression in admission (queue grows) reads differently from one in the
engine (service grows).
"""
from __future__ import annotations

import numpy as np

import repro.obs as obs
from benchmarks import common
from repro.serve import QueryProfile, SearchServer, loadgen

N_DISTINCT = 48
WORDS = 2
MAX_BATCH = 32
WORKERS = 64


def _traces(engine) -> int:
    return sum(engine.stats["traces"].values())


def run(bench: common.Bench | None = None, *, n_requests: int = 768,
        open_qps: float = 200.0, print_rows=print) -> dict:
    b = bench or common.build()
    engine = b.engine
    queries = loadgen.sample_queries(engine, N_DISTINCT, WORDS,
                                     df_range=(2, 2), seed=7)
    profile = QueryProfile(mode="or", strategy="drb", measure="bm25", k=10,
                           df_cap=engine.suggested_df_cap(queries))
    workload = [queries[i % N_DISTINCT] for i in range(n_requests)]
    results: dict = {"config": {"n_requests": n_requests, "words": WORDS,
                                "max_batch": MAX_BATCH, "workers": WORKERS,
                                "profile": "drb/or/bm25/k10"}}

    def emit(tag: str, rep, extra: str = ""):
        st = rep.server_stats
        derived = (f"qps={rep.qps:.0f};p50={rep.p50_ms:.2f}ms;"
                   f"p95={rep.p95_ms:.2f}ms;p99={rep.p99_ms:.2f}ms;"
                   f"shed={rep.n_shed};mean_batch={st['mean_batch']:.2f}"
                   + (";" + extra if extra else ""))
        print_rows(common.csv_row(f"table6/{tag}", rep.mean_ms * 1e3, derived))
        results[tag] = {"qps": rep.qps, "p50_ms": rep.p50_ms,
                        "p95_ms": rep.p95_ms, "p99_ms": rep.p99_ms,
                        "mean_ms": rep.mean_ms, "shed": rep.n_shed,
                        "queue_p50_ms": rep.queue_p50_ms,
                        "queue_p99_ms": rep.queue_p99_ms,
                        "service_p50_ms": rep.service_p50_ms,
                        "service_p99_ms": rep.service_p99_ms,
                        "mean_batch": st["mean_batch"],
                        "batch_hist": st["batch_hist"],
                        "cache_hit_rate": st["cache"]["hit_rate"]}
        if rep.stages:
            results[tag]["stages"] = rep.stages

    # -- 1. micro-batched closed loop ---------------------------------------
    srv = SearchServer(engine, max_batch=MAX_BATCH, max_wait_ms=2.0,
                       cache_size=0, queue_depth=4 * WORKERS)
    srv.warmup(queries, profile)
    t0 = _traces(engine)
    with srv:
        loadgen.closed_loop(srv, workload[:2 * WORKERS], n_workers=WORKERS,
                            profile=profile)          # measurement warm pass
        rep_batched = loadgen.closed_loop(srv, workload, n_workers=WORKERS,
                                          profile=profile)
    retraces = _traces(engine) - t0
    emit("closed_batched", rep_batched, f"retraces={retraces}")
    results["retraces_after_warmup"] = retraces
    # the documented pin, not just a recording: a compile on the query path
    # costs ~1 s — it must fail the benchmark loudly, never hide in the JSON
    assert retraces == 0, f"{retraces} executor retraces on the query path"

    # -- 2. one-query-at-a-time baseline ------------------------------------
    srv1 = SearchServer(engine, max_batch=1, max_wait_ms=0.0,
                        cache_size=0, queue_depth=4 * WORKERS)
    srv1.warmup(queries, profile)
    with srv1:
        loadgen.closed_loop(srv1, workload[:2 * WORKERS], n_workers=WORKERS,
                            profile=profile)
        rep_single = loadgen.closed_loop(srv1, workload, n_workers=WORKERS,
                                         profile=profile)
    speedup = rep_batched.qps / rep_single.qps if rep_single.qps else float("nan")
    emit("closed_single", rep_single, f"batched_speedup={speedup:.2f}x")
    results["batched_vs_single_speedup"] = speedup

    # -- 3. open loop at fixed offered load ---------------------------------
    srv_o = SearchServer(engine, max_batch=MAX_BATCH, max_wait_ms=2.0,
                         cache_size=0, queue_depth=4 * WORKERS)
    srv_o.warmup(queries, profile)
    with srv_o:
        rep_open = loadgen.open_loop(
            srv_o, workload, target_qps=open_qps, profile=profile, seed=7)
    emit(f"open_qps{open_qps:.0f}", rep_open)

    # -- 4. Zipf workload with the result cache -----------------------------
    srv_c = SearchServer(engine, max_batch=MAX_BATCH, max_wait_ms=2.0,
                         cache_size=256, queue_depth=4 * WORKERS)
    srv_c.warmup(queries, profile)
    zipf = loadgen.zipf_workload(queries, n_requests, seed=7)
    with srv_c:
        rep_cache = loadgen.closed_loop(srv_c, zipf, n_workers=WORKERS,
                                        profile=profile)
    emit("closed_cached", rep_cache,
         f"hit_rate={rep_cache.server_stats['cache']['hit_rate']:.2f}")

    # -- 5. observability pass: registry stages fed from the spans ---------
    reg = obs.Registry(enabled=True)
    profile_dr = QueryProfile(mode="or", strategy="dr", measure="tfidf",
                              k=10)
    srv_m = SearchServer(engine, max_batch=MAX_BATCH, max_wait_ms=2.0,
                         cache_size=0, queue_depth=4 * WORKERS, registry=reg)
    srv_m.warmup(queries, profile_dr)
    obs.enable_spans()
    try:
        with srv_m:
            rep_obs = loadgen.open_loop(
                srv_m, workload, target_qps=open_qps, profile=profile_dr,
                seed=7)
    finally:
        engine.obs_registry = None      # don't tax later benchmark passes
        obs.enable_spans(False)
        obs.SPANS.drain()
    emit("open_obs", rep_obs)
    assert rep_obs.stages and "device" in rep_obs.stages \
        and "queue_wait" in rep_obs.stages, \
        "obs-enabled pass produced no per-stage attribution"

    return results


if __name__ == "__main__":
    run()
