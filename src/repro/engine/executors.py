"""Jitted query executors behind the :class:`repro.engine.SearchEngine` facade.

One executor = one ``jax.jit``-compiled callable specialized on everything XLA
needs static: ``(backend, strategy, mode, measure, k, batch_shape, budget)``.
The facade caches executors by exactly that key, so repeated traffic with the
same shape hits an already-compiled program — the single place the ROADMAP's
serving path gets its compile-once/run-many behavior.

Trace accounting: each executor's Python body runs only when jax *traces* it
(once per compilation), so the ``note()`` callback it invokes counts actual
retraces.  ``SearchEngine.stats["traces"]`` exposes the counters and
``tests/test_engine.py`` pins the cache behavior with them.

Naming: each executor's jitted function is named after its path
(:func:`name_of`: ``dr_or``, ``drb_and``, ``positional_phrase``,
``sharded_dr_or``, ...), so a device profile's ``XLA Modules`` line reads
``jit_<name>`` for the program each batch ran.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax

from repro.core import distributed, drb, mega, positional, ranked


class ExecutorKey(NamedTuple):
    """Hashable cache key — everything that forces a distinct XLA program.

    The positional modes ("phrase" / "near") get distinct keys through
    ``mode``; the proximity ``window`` is deliberately *not* part of the key —
    it is a traced scalar, so every window width shares one compiled program.
    """
    backend: str          # "single" | "sharded"
    strategy: str         # "dr" | "drb" (post-"auto" resolution)
    mode: str             # "and" | "or" | "phrase" | "near"
    measure: Any          # frozen scoring dataclass (hashable, carries params)
    k: int
    batch_shape: tuple[int, int]   # (B, Q)
    budget: int | None    # DR max_pops
    df_cap: int | None    # DRB/OR gather width (pow2-bucketed); None otherwise
    beam_width: int       # frontier width P of the DR / DRB-AND loop cores;
                          # static (a distinct P is a distinct XLA program),
                          # normalized to 1 on the paths with no search loop
    mega: bool = False    # single-backend DR and/or only: run the batch on
                          # the pool-frontier core (core/mega.py) instead of
                          # vmapping the serial heap core; normalized False
                          # everywhere else so keys never split spuriously
    lowering: str = "ref" # resolved descent-kernel plan tag
                          # (kernels/backend.py KernelPlan.tag — "tpu",
                          # "gpu", "gpu:interpret", "ref", …): part of the
                          # key so a changed force/env/config can never hit
                          # an executor compiled under another lowering; on
                          # the mega path a "gpu*" tag additionally routes
                          # the loop body through the fused device-resident
                          # beam step (kernels/beam_step.py)


def name_of(key: ExecutorKey) -> str:
    """The executor's name, after its path: ``<strategy>_<mode>`` on the
    single backend (``_mega`` appended on the pool-frontier core),
    ``positional_<mode>`` for phrase/near, ``sharded_<strategy>_<mode>``."""
    if key.backend == "sharded":
        return f"sharded_{key.strategy}_{key.mode}"
    if key.mode in ("phrase", "near"):
        return f"positional_{key.mode}"
    return f"{key.strategy}_{key.mode}" + ("_mega" if key.mega else "")


def _jit(fn, key: ExecutorKey):
    fn.__name__ = fn.__qualname__ = name_of(key)
    return jax.jit(fn)


def make_single_dr(key: ExecutorKey, *, heap_cap: int, mega_cap: int, note):
    """(idx, words, wmask, idf) -> DRResult with (B, k) leaves."""
    conjunctive = key.mode == "and"

    if key.mega:
        # a gpu-kind lowering replaces the whole loop trip with ONE fused
        # beam-step launch; "tpu"/"ref" keep the jnp pool body (the descent
        # inside it still dispatches through kernels/ops.py)
        fused = key.lowering if key.lowering.startswith("gpu") else None

        def fn(idx, words, wmask, idf):
            note()
            return mega.topk_dr_mega(idx, words, wmask, idf, k=key.k,
                                     conjunctive=conjunctive, cap=mega_cap,
                                     max_pops=key.budget, fused=fused)
    else:
        def fn(idx, words, wmask, idf):
            note()
            return ranked.topk_dr_batch(idx, words, wmask, idf, k=key.k,
                                        conjunctive=conjunctive,
                                        heap_cap=heap_cap,
                                        max_pops=key.budget,
                                        beam_width=key.beam_width)

    return _jit(fn, key)


def make_single_drb(key: ExecutorKey, *, note):
    """(idx, aux, words, wmask, idf, avg_dl) -> DRResult with (B, k) leaves."""
    measure = key.measure
    if key.mode == "and":
        def one(idx, aux, w, m, idf, avg_dl):
            return drb.topk_drb_and(idx, aux, w, m, measure, k=key.k,
                                    idf=idf, avg_dl=avg_dl,
                                    beam_width=key.beam_width,
                                    max_pops=key.budget)
    else:
        def one(idx, aux, w, m, idf, avg_dl):
            return drb.topk_drb_or(idx, aux, w, m, measure, k=key.k,
                                   max_df_cap=key.df_cap, idf=idf,
                                   avg_dl=avg_dl)

    def fn(idx, aux, words, wmask, idf, avg_dl):
        note()
        return jax.vmap(
            lambda w, m: one(idx, aux, w, m, idf, avg_dl))(words, wmask)

    return _jit(fn, key)


def make_single_positional(key: ExecutorKey, *, note):
    """(idx, words, wmask, idf, window, avg_dl) -> PositionalResult with
    (B, k) leaves.  ``window`` is a traced int32 scalar (ignored by phrase),
    so proximity widths never force a retrace."""
    phrase = key.mode == "phrase"
    measure = key.measure

    def fn(idx, words, wmask, idf, window, avg_dl):
        note()
        return positional.topk_positional_batch(
            idx, words, wmask, idf, k=key.k, phrase=phrase, measure=measure,
            window=window, avg_dl=avg_dl)

    return _jit(fn, key)


def make_sharded(key: ExecutorKey, *, mesh, shard_axes, heap_cap: int, note):
    """(sharded, words, wmask, idf) -> DRResult with (B, k) leaves.  ``idf``
    is the measure-specific *global* table so sharded scores match the
    single-host backend for every measure, not just tf-idf."""
    method = f"{key.strategy}-{key.mode}"

    def fn(sharded, words, wmask, idf):
        note()
        return distributed.distributed_topk(
            sharded, words, wmask, k=key.k, method=method, mesh=mesh,
            shard_axes=shard_axes, heap_cap=heap_cap,
            max_df_cap=key.df_cap or 2, max_pops=key.budget,
            measure=key.measure, idf=idf, beam_width=key.beam_width)

    return _jit(fn, key)
