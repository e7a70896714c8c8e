"""Set-up of one cell in this process: the collection, the engine and a warm
``SearchServer``; and the count of programs compiled meanwhile."""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from lib import collection, traffic as traffic_lib

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Programs JAX compiled or loaded from its persistent cache, and
    functions it traced, counted by its monitoring events; ``fresh`` are the
    programs the cache did not hold."""

    def __init__(self):
        import jax
        self.programs = self.hits = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.programs += 1
        elif event == JAXPR_TRACE:
            self.traces += 1

    def _event(self, event, **kw):
        if event == CACHE_HIT:
            self.hits += 1

    @property
    def fresh(self) -> int:
        return self.programs - self.hits


@dataclasses.dataclass
class Session:
    coll: collection.Collection
    engine: object
    server: object
    profile: object
    queries: traffic_lib.Queries
    cw_len: np.ndarray        # codeword length of every word id (bytes)
    executors: int            # executors warmed
    phases: dict              # set-up phase -> seconds


def profile_of(traffic: dict):
    from repro.serve.batcher import QueryProfile
    return QueryProfile(**traffic["profile"])


def build(config: dict, traffic: dict, seed: int) -> Session:
    """Collection from the seed, engine on the default device, server warmed
    for every executor the mix can reach (batch buckets up to
    ``max_batch`` x the Q buckets of its query lengths); what it made is
    then frozen out of the garbage collector's reach."""
    import jax
    from repro.engine import EngineConfig, SearchEngine
    from repro.serve.server import SearchServer

    phases = {}
    t = time.monotonic()
    coll = collection.make(config, seed)
    coll.index()
    phases["collection"] = time.monotonic() - t

    t = time.monotonic()
    engine = SearchEngine.build(
        coll.doc_tokens(),
        EngineConfig(block=config["block"], with_drb=config["with_drb"]),
        vocab_size=coll.vocab_size)
    if config["with_drb"]:
        engine.aux                            # builds the DRB bitmaps
    jax.block_until_ready((engine.idx, engine._aux))
    phases["build"] = time.monotonic() - t

    t = time.monotonic()
    queries = traffic_lib.Queries(traffic, coll, seed)
    profile = profile_of(traffic)
    server = SearchServer(engine, max_batch=config["max_batch"],
                          max_wait_ms=traffic["max_wait_ms"],
                          queue_depth=config["queue_depth"], cache_size=0)
    warm = queries.warmup_set()
    executors = server.warmup(warm, profile)
    with server:                     # one pass through the serving threads
        for q in warm:
            server.search(q, profile, timeout=600.0)
    phases["warmup"] = time.monotonic() - t
    rank_of_word = np.asarray(engine.model.rank_of_word)
    cw_len = np.asarray(engine.idx.cw_len)[rank_of_word]
    # what set-up made lives as long as the server: keep the collector from
    # walking it in the window (a full collection holds every thread)
    gc.collect()
    gc.freeze()
    return Session(coll=coll, engine=engine,
                   server=server, profile=profile, queries=queries,
                   cw_len=cw_len, executors=executors, phases=phases)
