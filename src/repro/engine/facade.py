"""`SearchEngine` — the one public query API over every retrieval backend.

The paper frames WTBC-DR ("no extra space") and WTBC-DRB ("a few small
bitmaps") as interchangeable strategies answering the same ranked top-k
queries; the repo additionally runs both over document-sharded device meshes.
Before this facade, every caller re-assembled the same glue by hand: word-id
-> frequency-rank mapping, ragged-query padding and masking, idf tables,
``heap_cap`` / ``max_df_cap`` derivation, DR/BM25 compatibility checks, vmap
wiring, shard merges.  ``SearchEngine`` owns all of it:

    engine = SearchEngine.build(doc_tokens)            # or .shard(..., n_shards=8)
    res = engine.search([[w1, w2], [w3]], k=10, mode="and")
    print(res.hits(0), engine.snippets(res, length=8))
    res = engine.search([[w1, w2]], mode="phrase")     # or mode="near", window=6
    print(res.matches(0))                              # (doc, score, pos, len)

Dispatch goes through jitted executors cached by
``(strategy, mode, measure, k, batch_shape, budget, df_cap)`` (see
executors.py), so steady-state traffic never retraces.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import types
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import distributed, drb, positional, scoring, wtbc
from repro.engine import executors
from repro.kernels import backend as kernel_backend
from repro.engine.config import EngineConfig, SLA_CLASSES
from repro.engine.results import SearchResults

MODES = ("and", "or", "phrase", "near")
POSITIONAL_MODES = ("phrase", "near")
STRATEGIES = ("dr", "drb", "auto")
MEASURES = {"tfidf": scoring.TfIdf(), "bm25": scoring.BM25()}

# cold-start pop cost (µs) assumed by the deadline -> budget conversion until
# the engine has observed real traffic (see SearchEngine.us_per_pop);
# deliberately pessimistic so a deadline is honored even before warmup
DEFAULT_US_PER_POP = 50.0


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (n >= 1) — the shared shape-bucket policy:
    executor keys quantize the query-word dim Q (and the serving batcher the
    batch dim B) to these buckets, so mixed traffic reuses a small fixed set
    of compiled programs instead of one program per exact shape."""
    return 1 << max(0, int(n) - 1).bit_length()


def budget_bucket(n: int) -> int:
    """Largest power of FOUR <= n (n >= 1) — the anytime-budget quantizer.
    ``budget`` is static in the executor key (the loop bound is compiled in),
    so a deadline-derived budget — which drifts with the live us/pop estimate
    — must be quantized or every estimate update would compile a fresh
    program.  Powers of four keep the whole useful range [1, 2*n_docs) within
    a handful of buckets while never overshooting the deadline (floor, not
    ceil: rounding the budget *down* can only finish earlier)."""
    n = max(1, int(n))
    return 1 << ((n.bit_length() - 1) & ~1)


def _normalize_docs(docs, vocab_size: int | None):
    """Accept a corpus object (``.doc_tokens`` / ``.vocab_size``) or a plain
    list of per-document word-id arrays; return (list[np.ndarray], vocab_size).
    Word id 0 is the reserved document separator '$'."""
    if hasattr(docs, "doc_tokens") and hasattr(docs, "vocab_size"):
        if vocab_size is not None and vocab_size < int(docs.vocab_size):
            raise ValueError(f"vocab_size={vocab_size} smaller than the "
                             f"corpus's own vocab_size={docs.vocab_size}")
        return list(docs.doc_tokens), int(vocab_size or docs.vocab_size)
    doc_tokens = [np.asarray(d, dtype=np.int64) for d in docs]
    if not doc_tokens:
        raise ValueError("cannot build an engine over zero documents")
    max_id = max((int(d.max()) for d in doc_tokens if len(d)), default=0)
    for d in doc_tokens:
        if len(d) and int(d.min()) < 1:
            raise ValueError("word id 0 is reserved for the '$' separator; "
                             "document ids must be >= 1")
    if vocab_size is None:
        vocab_size = max_id + 1
    elif vocab_size <= max_id:
        raise ValueError(f"vocab_size={vocab_size} too small for max word id "
                         f"{max_id}")
    return doc_tokens, int(vocab_size)


class SearchEngine:
    """Facade over the DR / DRB / sharded retrieval backends.

    Construct with :meth:`build` (single index) or :meth:`shard`
    (document-sharded mesh); query with :meth:`search`; recover text around
    the hits with :meth:`snippets`.  Instances are cheap handles around
    immutable device arrays — share one per corpus.
    """

    def __init__(self, *, _token=None, config, model, n_docs, backend,
                 idx=None, doc_tokens=None, sharded=None, mesh=None,
                 shard_axes=None):
        if _token is not _CTOR_TOKEN:
            raise TypeError("use SearchEngine.build(...) or "
                            "SearchEngine.shard(...)")
        self.config = config
        self.model = model
        self.n_docs = n_docs
        self.backend = backend                  # "single" | "sharded"
        self._idx = idx
        # kept only until the lazy DRB build can no longer happen — pinning
        # the raw tokens forever would defeat the paper's "no space" premise
        self._doc_tokens = doc_tokens if config.with_drb else None
        self._aux = None
        self._sharded = sharded
        self._mesh = mesh
        self._shard_axes = shard_axes
        self._idf_tables: dict[str, jnp.ndarray] = {}
        self._avg_dl = None
        self._executors: dict[executors.ExecutorKey, Any] = {}
        self._trace_counts: dict[executors.ExecutorKey, int] = {}
        self._us_per_pop: float | None = None   # EWMA, None until observed
        self._stats_lock = threading.Lock()     # _executors/_trace_counts/EWMA
        # None -> record into the live process default (obs.enable()/use());
        # the serving frontend pins its own registry here on adoption
        self.obs_registry: "obs.Registry | None" = None
        self._shard_slices: dict[int, wtbc.WTBCIndex] = {}
        if backend == "single":
            self._heap_cap = 2 * int(idx.n_docs) + 4
            self._df_np = np.asarray(idx.df)
            # pool-frontier cap: the split tree's frontier holds <= n_docs
            # segments (each split removes 1, adds <= 2, over < n_docs
            # splits), so n_docs + 2 can never overflow (DESIGN.md §8)
            self._mega_cap = int(idx.n_docs) + 2
        else:
            self._heap_cap = 2 * int(np.max(np.asarray(sharded.idx.n_docs))) + 4
            # per-word max over shards: any shard's DRB/OR gather fits the cap
            self._df_np = np.asarray(sharded.idx.df).max(axis=0)
            self._mega_cap = 0          # mega covers the single backend only
        self._max_df_cap = int(self._df_np.max()) + 2
        self._content_tag: int | None = None

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, docs, config: EngineConfig | None = None, *,
              vocab_size: int | None = None) -> "SearchEngine":
        """Build a single-host engine over ``docs`` (a corpus object or a list
        of per-document word-id arrays, ids >= 1)."""
        config = config or EngineConfig()
        doc_tokens, vocab_size = _normalize_docs(docs, vocab_size)
        idx, model = wtbc.build_index(doc_tokens, vocab_size,
                                      block=config.block)
        return cls(_token=_CTOR_TOKEN, config=config, model=model,
                   n_docs=len(doc_tokens), backend="single", idx=idx,
                   doc_tokens=doc_tokens)

    @classmethod
    def shard(cls, docs, n_shards: int, config: EngineConfig | None = None, *,
              vocab_size: int | None = None, mesh=None,
              shard_axes: str | tuple[str, ...] = "shards") -> "SearchEngine":
        """Build a document-sharded engine: one WTBC per device along
        ``shard_axes`` of ``mesh`` (a 1-D mesh over the first ``n_shards``
        local devices when ``mesh`` is omitted), global (s,c)-DC code and
        global idf so shard scores merge exactly.  Each device receives only
        its own shard (``distributed.place``)."""
        config = config or EngineConfig()
        doc_tokens, vocab_size = _normalize_docs(docs, vocab_size)
        sharded, model = distributed.build_sharded(
            doc_tokens, vocab_size, n_shards=n_shards, block=config.block,
            with_drb=config.with_drb, eps=config.eps)
        if mesh is None:
            axes = (shard_axes,) if isinstance(shard_axes, str) else tuple(shard_axes)
            if len(axes) != 1:
                raise ValueError("pass an explicit mesh for multi-axis sharding")
            devices = jax.devices()
            if len(devices) < n_shards:
                raise ValueError(f"n_shards={n_shards} exceeds the "
                                 f"{len(devices)} available devices; pass a mesh")
            mesh = jax.sharding.Mesh(
                np.array(devices[:n_shards]).reshape(n_shards), axes)
        sharded = distributed.place(sharded, mesh, shard_axes)
        return cls(_token=_CTOR_TOKEN, config=config, model=model,
                   n_docs=len(doc_tokens), backend="sharded", sharded=sharded,
                   mesh=mesh, shard_axes=shard_axes)

    @classmethod
    def _restore(cls, *, config, model, n_docs, backend, idx=None, aux=None,
                 sharded=None, mesh=None, shard_axes=None) -> "SearchEngine":
        """Reassemble an engine from snapshot parts (``repro.serve.snapshot``)
        — no corpus, no rebuild; the restored arrays ARE the engine."""
        self = cls(_token=_CTOR_TOKEN, config=config, model=model,
                   n_docs=n_docs, backend=backend, idx=idx, doc_tokens=None,
                   sharded=sharded, mesh=mesh, shard_axes=shard_axes)
        if aux is not None:
            self._aux = aux
        return self

    # -- lazily-derived state ------------------------------------------------

    @property
    def idx(self) -> wtbc.WTBCIndex:
        """The single-host index (stacked per-shard index when sharded)."""
        return self._idx if self.backend == "single" else self._sharded.idx

    @property
    def aux(self) -> drb.DRBAux:
        """DRB tf bitmaps, built on first use (single backend)."""
        if self.backend != "single":
            return self._sharded.aux
        if self._aux is None:
            if not self.config.with_drb:
                raise ValueError("this engine was built with with_drb=False; "
                                 "DRB (and BM25) queries are unavailable")
            if self._doc_tokens is None:
                raise ValueError("DRB bitmaps unavailable: this engine was "
                                 "restored without them (snapshot.save builds "
                                 "them first when config.with_drb)")
            self._aux = drb.build_aux(self._idx, self.model, self._doc_tokens,
                                      eps=self.config.eps)
            self._doc_tokens = None     # raw tokens no longer needed
        return self._aux

    def _idf_table(self, measure) -> jnp.ndarray:
        """Per-measure idf table; on the sharded backend it is derived from
        the *global* document frequencies (a shard's local df would make
        shard scores incomparable)."""
        if measure.name not in self._idf_tables:
            if self.backend == "single":
                stats = self._idx
            else:
                stats = types.SimpleNamespace(
                    df=self._sharded.global_df,
                    n_docs=jnp.int32(self.n_docs))
            self._idf_tables[measure.name] = measure.idf(stats)
        return self._idf_tables[measure.name]

    @property
    def content_tag(self) -> int:
        """CRC32 fingerprint of what this engine would *answer with*: the
        config plus the index's document-frequency, separator-position and
        document-length tables.  Two engines with equal tags serve equal
        corpora under equal settings; the serving cache versions its keys
        with this so an ``swap_engine`` can never replay a stale hit — and a
        snapshot-restored engine naturally inherits the tag of the engine it
        was saved from (the arrays ARE the content)."""
        if self._content_tag is None:
            idx = self.idx
            h = zlib.crc32(repr(dataclasses.astuple(self.config)).encode())
            for leaf in (self._df_np, np.asarray(idx.sep_pos),
                         np.asarray(idx.doc_len)):
                h = zlib.crc32(np.ascontiguousarray(leaf), h)
            self._content_tag = h
        return self._content_tag

    def _avg_doc_len(self) -> jnp.ndarray:
        if self._avg_dl is None:
            idx = self._idx
            self._avg_dl = (jnp.sum(idx.doc_len.astype(jnp.float32))
                            / idx.n_docs.astype(jnp.float32))
        return self._avg_dl

    # -- query normalization -------------------------------------------------

    def _encode_queries(self, queries) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Word ids (array or ragged lists) -> padded (B, Q) frequency ranks
        + validity mask.  A single flat query becomes a batch of one.

        Q is padded up to a power-of-two bucket (extra columns masked out), so
        batches whose longest query differs only within a bucket share one
        compiled executor — the serving batcher coalesces mixed-length traffic
        relying on exactly this.  Masked columns are ignored by every backend
        (the invariant ragged queries already depend on), so bucketing never
        changes results."""
        if hasattr(queries, "ndim") or (
                len(queries) and np.isscalar(queries[0])):
            arr = np.asarray(queries, dtype=np.int64)
            if arr.ndim == 1:
                arr = arr[None, :]
            if arr.ndim != 2:
                raise ValueError(f"queries must be (B, Q) or (Q,), got shape "
                                 f"{arr.shape}")
            mask = np.ones(arr.shape, dtype=bool)
        else:
            rows = [np.asarray(q, dtype=np.int64).reshape(-1) for q in queries]
            if not rows:
                raise ValueError("empty query batch")
            Q = max((len(r) for r in rows), default=0)
            if Q == 0:
                raise ValueError("all queries are empty")
            arr = np.zeros((len(rows), Q), dtype=np.int64)
            mask = np.zeros((len(rows), Q), dtype=bool)
            for b, r in enumerate(rows):
                arr[b, :len(r)] = r
                mask[b, :len(r)] = True
        V = self.model.vocab_size
        bad = mask & ((arr < 1) | (arr >= V))
        if bad.any():
            raise ValueError(f"query word ids must be in [1, {V}); offending "
                             f"ids: {sorted(set(arr[bad].tolist()))[:10]}")
        Qb = pow2_bucket(arr.shape[1])
        if Qb != arr.shape[1]:
            arr = np.pad(arr, ((0, 0), (0, Qb - arr.shape[1])))
            mask = np.pad(mask, ((0, 0), (0, Qb - mask.shape[1])))
        ranks = np.where(mask, self.model.rank_of_word[arr], 0)
        return ranks.astype(np.int32), mask

    # -- dispatch ------------------------------------------------------------

    def _resolve_measure(self, measure):
        if isinstance(measure, str):
            try:
                return MEASURES[measure]
            except KeyError:
                raise ValueError(f"unknown measure {measure!r}; expected one "
                                 f"of {sorted(MEASURES)} or a scoring object")
        for attr in ("name", "dr_compatible", "idf", "score"):
            if not hasattr(measure, attr):
                raise ValueError(f"measure object lacks .{attr}")
        return measure

    def _resolve_strategy(self, strategy: str, measure, budget,
                          mode: str = "and") -> str:
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {strategy!r}; expected one of "
                             f"{STRATEGIES}")
        if mode in POSITIONAL_MODES:
            # phrase/near run on the bare WTBC (locate/decode walks) — the
            # "no extra space" family; DRB bitmaps carry no positions.  Any
            # additive measure works: documents are fully materialized before
            # scoring, so DR's monotonicity restriction does not apply.
            if strategy == "drb":
                raise ValueError(f"mode={mode!r} runs on the bare WTBC; use "
                                 "strategy='dr' or 'auto'")
            if budget is not None:
                raise ValueError("budget (any-time max_pops) applies to the "
                                 "and/or DR strategy only")
            return "dr"
        if strategy == "auto":
            strategy = "dr" if measure.dr_compatible else "drb"
        if strategy == "dr":
            scoring.assert_dr_compatible(measure)   # BM25 + "dr" -> ValueError
        elif not self.config.with_drb:
            raise ValueError("this engine was built with with_drb=False; "
                             "only strategy='dr' is available")
        # DRB/AND honors budget (candidate-iteration cap, all-or-nothing
        # certification); the loop-free DRB/OR path normalizes it off
        # post-routing in search() — one serving profile carries the knob
        # across strategy routing without erroring on the exact paths.
        return strategy

    def _df_cap(self, ranks: np.ndarray, mask: np.ndarray) -> int:
        """DRB/OR gather width: max df among the query words (+2 slack),
        rounded up to a power of two so nearby workloads share one compiled
        executor instead of retracing per batch."""
        m = int(self._df_np[ranks[mask]].max()) if mask.any() else 1
        cap = 1 << int(m + 2 - 1).bit_length()
        return min(cap, self._max_df_cap)

    # -- anytime cost model (DESIGN.md §11) ----------------------------------

    def note_cost(self, seconds: float, pops_per_row: float) -> None:
        """Feed the live us/pop estimator one observed batch: ``seconds`` of
        blocking wall time against the mean per-row pop count (rows run
        vmapped in parallel, so the per-row count is what the wall clock
        tracks).  Called from the observed search path and from the serving
        dispatcher; EWMA so bursts move it quickly but one straggler does
        not poison the estimate."""
        if pops_per_row <= 0 or seconds <= 0:
            return
        us = seconds * 1e6 / float(pops_per_row)
        with self._stats_lock:
            prev = self._us_per_pop
            self._us_per_pop = us if prev is None else 0.8 * prev + 0.2 * us

    @property
    def us_per_pop(self) -> float:
        """Live cost estimate (µs of wall time per heap pop per row);
        ``DEFAULT_US_PER_POP`` until real traffic has been observed."""
        with self._stats_lock:
            est = self._us_per_pop
        return DEFAULT_US_PER_POP if est is None else est

    def budget_for_deadline(self, deadline_ms: float) -> int | None:
        """Pop budget affordable within ``deadline_ms`` at the live us/pop
        estimate, floor-quantized to a :func:`budget_bucket` so estimate
        drift never recompiles.  Returns None when the exhaustive search
        provably fits the deadline (a DR search pops < 2*n_docs + 2 segments
        — each split consumes one and adds at most two over < n_docs splits)
        — the caller then runs the plain exact executor, no key split."""
        pops = int(float(deadline_ms) * 1e3 / self.us_per_pop)
        if pops >= 2 * self.n_docs + 2:
            return None
        return budget_bucket(max(1, pops))

    @property
    def _obs(self) -> "obs.Registry":
        """The registry this engine records into: an explicitly adopted one
        (``obs_registry``, set by the serving frontend), else the *live*
        process default — looked up per call so ``obs.enable()``/``obs.use``
        after engine construction still take effect."""
        return self.obs_registry if self.obs_registry is not None \
            else obs.default_registry()

    def _executor(self, key: executors.ExecutorKey):
        with self._stats_lock:
            ex = self._executors.get(key)
        if ex is None:
            def note():
                with self._stats_lock:
                    self._trace_counts[key] = \
                        self._trace_counts.get(key, 0) + 1
                self._obs.counter(
                    "repro_engine_traces_total",
                    {"backend": key.backend, "strategy": key.strategy,
                     "mode": key.mode},
                    "executor jit traces (growth after warmup = key churn)",
                ).inc()
            if key.backend == "sharded":
                ex = executors.make_sharded(
                    key, mesh=self._mesh, shard_axes=self._shard_axes,
                    heap_cap=self._heap_cap, note=note)
            elif key.mode in POSITIONAL_MODES:
                ex = executors.make_single_positional(key, note=note)
            elif key.strategy == "dr":
                ex = executors.make_single_dr(key, heap_cap=self._heap_cap,
                                              mega_cap=self._mega_cap,
                                              note=note)
            else:
                ex = executors.make_single_drb(key, note=note)
            with self._stats_lock:
                ex = self._executors.setdefault(key, ex)
        return ex

    def suggested_df_cap(self, queries) -> int:
        """The DRB/OR gather width ``search`` would derive for ``queries`` —
        pass it back as ``search(..., df_cap=...)`` (or into a serving
        profile) to pin every batch drawn from the same word population onto
        one compiled executor regardless of which words each batch mixes."""
        ranks, mask = self._encode_queries(queries)
        return self._df_cap(ranks, mask)

    def warmup(self, queries, *, max_batch: int = 1, k: int | None = None,
               mode: str = "and", strategy: str = "auto", measure="tfidf",
               budget: int | None = None, sla: str | None = None,
               window: int | None = None,
               beam_width: int | None = None,
               df_cap: int | None = None,
               mega: bool | None = None) -> int:
        """Compile every executor the given traffic profile can hit before
        admitting traffic: one program per (batch bucket <= pow2(max_batch),
        Q bucket present in ``queries``).  Runs one real (tiny) search per
        shape, so after ``warmup`` steady-state traffic of this profile never
        retraces (``stats['traces']`` is the proof).  Returns the number of
        newly compiled executors.

        For ``strategy='drb', mode='or'`` pass an explicit ``df_cap``
        (e.g. :meth:`suggested_df_cap` of the serving word population) —
        otherwise the gather width is re-derived per batch and a heavier
        batch than any warmed one would still compile on the fly.
        """
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if hasattr(queries, "ndim") or (
                len(queries) and np.isscalar(queries[0])):
            arr = np.asarray(queries)
            rows = list(arr[None, :] if arr.ndim == 1 else arr)
        else:
            rows = [np.asarray(q).reshape(-1) for q in queries]
        reps = {}                       # Q bucket -> one representative row
        for r in rows:
            reps.setdefault(pow2_bucket(max(1, len(r))), r)
        before = sum(self._trace_counts.values())
        kw = dict(k=k, mode=mode, strategy=strategy, measure=measure,
                  budget=budget, sla=sla, window=window,
                  beam_width=beam_width, df_cap=df_cap, mega=mega)
        n_b = pow2_bucket(max_batch).bit_length()     # 1, 2, 4, ..., bucket
        for r in reps.values():
            row = [int(w) for w in r]
            for bb in (1 << i for i in range(n_b)):
                self.search([row] * bb, **kw)
        return sum(self._trace_counts.values()) - before

    def search(self, queries, *, k: int | None = None, mode: str = "and",
               strategy: str = "auto", measure="tfidf",
               budget: int | None = None,
               deadline_ms: float | None = None,
               sla: str | None = None,
               window: int | None = None,
               beam_width: int | None = None,
               df_cap: int | None = None,
               mega: bool | None = None) -> SearchResults:
        """Ranked top-k retrieval.

        queries:  (B, Q) / (Q,) array of word ids, or ragged lists of ids.
        k:        results per query (default: ``config.default_k``).
        mode:     "and" (conjunctive), "or" (bag-of-words), "phrase" (exact
                  consecutive in-order match), or "near" (all words within a
                  ``window``-token span).  phrase/near results additionally
                  carry match positions — see ``SearchResults.matches``.
        strategy: "dr" (no extra space), "drb" (tf bitmaps), or "auto" —
                  DR when the measure allows it, else DRB (e.g. BM25).
                  phrase/near always run on the bare WTBC ("dr").
        measure:  "tfidf", "bm25", or a scoring object.
        budget:   anytime work budget (per shard when sharded): DR heap pops /
                  DRB-AND candidate iterations; exact search when None.
                  Results carry per-slot ``certified`` bits and a
                  ``score_bound`` for whatever the budget cut off (DESIGN.md
                  §11); a budget that never binds is bitwise identical to
                  the exact search.  Normalized off on the loop-free DRB/OR
                  path; rejected on phrase/near (always exhaustive).
        deadline_ms: wall-clock target converted to a ``budget`` via the live
                  us/pop estimate (:meth:`budget_for_deadline`), quantized
                  to pow-4 buckets so estimate drift never recompiles.
                  Combines with an explicit ``budget`` by min.  Advisory,
                  not a hard timer — the loop bound is compiled in, the
                  engine never interrupts a running kernel.
        sla:      "exact", "bounded", or "best_effort" (default:
                  ``config.default_sla``, auto-promoted to "bounded" when
                  ``budget``/``deadline_ms`` is given).  "exact" *rejects*
                  anytime knobs — callers pinning sla="exact" can never be
                  silently degraded; "bounded" and "best_effort" differ only
                  in how the serving layer treats them under load (the
                  engine itself runs them identically).
        window:   proximity width in tokens, mode="near" only (default:
                  ``config.default_window``).  Traced — varying it reuses
                  the compiled executor.
        beam_width: frontier width P of the looped search cores (DR and/or,
                  DRB and; default ``config.default_beam_width``).  Each
                  iteration pops/verifies P candidates and batches their
                  rank workload into one fused call; P=1 is the classical
                  exact pop order, P>1 keeps results exact while cutting
                  loop trips ~P-fold (DESIGN.md §6).  Static, like ``k`` —
                  each distinct P compiles once and is cached.  Ignored
                  (normalized to 1) by the loop-free DRB/OR path; not
                  applicable to phrase/near.
        df_cap:   explicit DRB/OR gather width (static, pow2-bucketed and
                  clamped to the engine max).  By default the width is
                  derived from the batch's heaviest word, which makes the
                  executor key content-dependent — mixed traffic then
                  compiles one program per df bucket it happens to hit.
                  Serving pins this with :meth:`suggested_df_cap` so all
                  traffic shares one program.  Exactness-guarded: a cap
                  smaller than the batch actually needs raises instead of
                  silently truncating the gather.  DRB/OR only.
        mega:     run the batch on the pool-frontier megabatch core
                  (core/mega.py, DESIGN.md §8) instead of vmapping the
                  serial heap core (default ``config.default_mega``).
                  Row-for-row bitwise equal at the same Q bucket; the win
                  is throughput — per-row heap sifts under ``vmap`` lower
                  to whole-buffer scatters.  Applies to single-backend DR
                  and/or only and forces ``beam_width=1`` (the batch dim IS
                  the frontier parallelism); silently normalized off on
                  the paths it does not cover (DRB, positional, sharded),
                  so one serving profile can carry it across strategies.
        """
        c = self._prepare(queries, k=k, mode=mode, strategy=strategy,
                          measure=measure, budget=budget,
                          deadline_ms=deadline_ms, sla=sla, window=window,
                          beam_width=beam_width, df_cap=df_cap, mega=mega)
        reg = self._obs
        t0 = time.perf_counter() if reg.enabled else 0.0
        res = c.ex(*c.args)
        match_pos = match_len = None
        if c.key.mode in POSITIONAL_MODES:
            match_pos, match_len = res.match_pos, res.match_len
        if reg.enabled:
            self._record_search(reg, c.key, res, c.key.batch_shape, t0)
        return SearchResults(docs=res.docs, scores=res.scores,
                             n_found=res.n_found, work=res.iters, k=c.key.k,
                             mode=c.key.mode, strategy=c.key.strategy,
                             measure=c.key.measure.name,
                             match_pos=match_pos, match_len=match_len,
                             beam_width=c.key.beam_width,
                             pops=getattr(res, "pops", None),
                             overflowed=getattr(res, "overflowed", None),
                             padded=getattr(res, "padded", None),
                             certified=getattr(res, "certified", None),
                             score_bound=getattr(res, "bound", None),
                             sla=c.sla)

    def lower(self, queries, **kwargs) -> "jax.stages.Lowered":
        """The lowered program :meth:`search` would run for ``queries`` under
        the same keyword arguments — e.g. to check which kernels it calls
        (``lower(...).as_text()``).  Shares the executor cache with
        ``search``: lowering an executor that has run does not retrace it."""
        c = self._prepare(queries, **kwargs)
        return c.ex.lower(*c.args)

    def _prepare(self, queries, *, k: int | None = None, mode: str = "and",
                 strategy: str = "auto", measure="tfidf",
                 budget: int | None = None,
                 deadline_ms: float | None = None,
                 sla: str | None = None,
                 window: int | None = None,
                 beam_width: int | None = None,
                 df_cap: int | None = None,
                 mega: bool | None = None) -> types.SimpleNamespace:
        """Validate and normalise one :meth:`search` call into its executor
        key, executor and argument tuple (``ex(*args)`` runs it)."""
        k = self.config.default_k if k is None else int(k)
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
        if sla is not None and sla not in SLA_CLASSES:
            raise ValueError(f"unknown sla {sla!r}; expected one of "
                             f"{SLA_CLASSES}")
        if deadline_ms is not None and float(deadline_ms) <= 0:
            raise ValueError(f"deadline_ms must be positive, got {deadline_ms}")
        anytime = budget is not None or deadline_ms is not None
        sla = sla or ("bounded" if anytime else self.config.default_sla)
        if sla == "exact" and anytime:
            raise ValueError("sla='exact' guarantees an uninterrupted search "
                             "— budget/deadline_ms require sla='bounded' or "
                             "'best_effort'")
        if deadline_ms is not None:
            db = self.budget_for_deadline(deadline_ms)
            if db is not None:
                budget = db if budget is None else min(int(budget), db)
        if mode == "near":
            window = self.config.default_window if window is None else int(window)
            if window < 1:
                raise ValueError(f"window must be >= 1, got {window}")
        elif window is not None:
            raise ValueError(f"window applies to mode='near' only "
                             f"(got mode={mode!r})")
        m = self._resolve_measure(measure)
        if mode in POSITIONAL_MODES and deadline_ms is not None:
            raise ValueError("deadline_ms applies to the anytime and/or "
                             f"search cores only (got mode={mode!r}); "
                             "positional searches are always exhaustive")
        strat = self._resolve_strategy(strategy, m, budget, mode)
        if budget is not None:
            budget = int(budget)
            if budget < 1:
                raise ValueError(f"budget must be >= 1, got {budget}")
            if strat == "drb" and mode == "or":
                budget = None   # loop-free gather: always complete/certified
            elif budget >= 2 * self.n_docs + 2:
                budget = None   # can never bind: run the plain exact program
        if mode in POSITIONAL_MODES:
            if beam_width is not None:
                raise ValueError("beam_width applies to the looped and/or "
                                 f"search cores only (got mode={mode!r})")
            if self.backend == "sharded":
                raise ValueError(f"mode={mode!r} is not yet supported on the "
                                 "sharded backend; build a single-host engine")
            # positional top-k is a dense lax.top_k over the doc table
            k = min(k, self.n_docs)
        if beam_width is None:
            beam_width = self.config.default_beam_width
        elif int(beam_width) < 1:
            raise ValueError(f"beam_width must be >= 1, got {beam_width}")
        beam_width = int(beam_width)
        if mode in POSITIONAL_MODES or (strat == "drb" and mode == "or"):
            beam_width = 1          # no search loop: don't split the executor
        if mega is None:
            mega = self.config.default_mega
        # the mega core covers single-backend DR and/or; elsewhere normalize
        # it off (not an error: serving profiles carry one flag across
        # strategy routing) so executor keys never split spuriously
        mega = bool(mega) and (self.backend == "single" and strat == "dr"
                               and mode in ("and", "or"))
        if mega:
            beam_width = 1      # one pop per row: the batch dim IS the beam
        ranks, mask = self._encode_queries(queries)
        if strat == "drb" and mode == "or":
            auto_cap = self._df_cap(ranks, mask)
            if df_cap is None:
                df_cap = auto_cap
            else:
                df_cap = min(pow2_bucket(int(df_cap)), self._max_df_cap)
                if df_cap < auto_cap:
                    raise ValueError(
                        f"df_cap={df_cap} is smaller than the {auto_cap} this "
                        "batch's heaviest word needs — the gather would "
                        "silently truncate; pass a cap derived from "
                        "suggested_df_cap over the full word population")
        elif df_cap is not None:
            raise ValueError("df_cap applies to the DRB/OR gather path only "
                             f"(got strategy={strat!r}, mode={mode!r})")
        # resolve the descent-kernel lowering OUTSIDE the trace: the tag is
        # part of the executor key, so flipping a force/env (or an engine
        # built with another config.kernel_backend) compiles its own program
        # instead of replaying one lowered differently
        lowering = kernel_backend.descent_plan(self.config.kernel_backend
                                               if self.config.kernel_backend
                                               != "auto" else None).tag
        key = executors.ExecutorKey(self.backend, strat, mode, m, k,
                                    tuple(ranks.shape), budget, df_cap,
                                    beam_width, mega, lowering)
        ex = self._executor(key)
        words, wmask = jnp.asarray(ranks), jnp.asarray(mask)
        if mode in POSITIONAL_MODES:
            args = (self.idx, words, wmask, self._idf_table(m),
                    jnp.int32(window or 0), self._avg_doc_len())
        elif self.backend == "sharded":
            args = (self._sharded, words, wmask, self._idf_table(m))
        elif strat == "dr":
            args = (self.idx, words, wmask, self._idf_table(m))
        else:
            args = (self.idx, self.aux, words, wmask, self._idf_table(m),
                    self._avg_doc_len())
        return types.SimpleNamespace(key=key, ex=ex, args=args, sla=sla)

    def _record_search(self, reg: "obs.Registry", key, res, shape, t0):
        """Registry side of one observed search (enabled registries only):
        per-(backend, strategy, mode) dispatch counters, per-row work
        histograms (trips/pops/pad-waste), and the live WTBC query-roofline
        gauges.  Forces device completion first — the wall time must cover
        the compute, not just its dispatch — which is why the disabled path
        skips this method entirely (DESIGN.md §10 overhead budget)."""
        jax.block_until_ready(res.docs)
        dt = time.perf_counter() - t0
        B, Q = int(shape[0]), int(shape[1])
        labels = {"backend": key.backend, "strategy": key.strategy,
                  "mode": key.mode}
        reg.counter("repro_engine_searches_total", labels,
                    "search batches dispatched").inc()
        reg.counter("repro_engine_rows_total", labels,
                    "query rows searched").inc(B)
        reg.histogram("repro_engine_dispatch_seconds", labels,
                      "blocking wall time per search batch").observe(dt)
        reg.gauge("repro_engine_executors", None,
                  "compiled executors cached").set(len(self._executors))
        work = np.asarray(res.iters).ravel()
        reg.histogram("repro_engine_trips", labels,
                      "search-loop trips per query row"
                      ).observe_many(work.tolist())
        pops = getattr(res, "pops", None)
        padded = getattr(res, "padded", None)
        if pops is not None:
            pops = np.asarray(pops).ravel()
            reg.histogram("repro_engine_pops", labels,
                          "candidate pops per query row"
                          ).observe_many(pops.tolist())
            if key.budget is None and len(pops):
                # feed the deadline->budget estimator from *unbudgeted*
                # batches only: a budget-cut batch's wall time hides the
                # harvest tail and would bias us/pop optimistic
                self.note_cost(dt, float(pops.mean()))
            reg.gauge("repro_engine_us_per_pop", None,
                      "live pop cost estimate feeding deadline budgets"
                      ).set(self.us_per_pop)
        if padded is not None:
            padded = np.asarray(padded).ravel()
            reg.histogram("repro_engine_pad_lanes", labels,
                          "dead beam lanes per query row (pad waste)"
                          ).observe_many(padded.tolist())
        if pops is not None and len(pops):
            from repro.analysis import roofline
            rl = roofline.wtbc_query_roofline(
                device_kind=jax.devices()[0].device_kind,
                lowering=key.lowering,
                measured_us_per_query=dt * 1e6 / max(B, 1),
                pops=float(pops.mean()),
                padded=float(padded.mean()) if padded is not None else 0.0,
                q=Q, block=int(self.config.block))
            roofline.live_wtbc_gauges(rl, reg)

    # -- post-processing -----------------------------------------------------

    def _local_index(self, doc: int):
        """Map a global doc id to (per-shard index pytree, local doc id).
        Shard slices are memoized — slicing the stacked pytree materializes a
        copy of every leaf, so pay it once per shard, not once per hit."""
        if self.backend == "single":
            return self._idx, doc
        base = np.asarray(self._sharded.doc_base)
        s = int(np.searchsorted(base, doc, side="right")) - 1
        if s not in self._shard_slices:
            self._shard_slices[s] = jax.tree.map(lambda x: x[s],
                                                 self._sharded.idx)
        return self._shard_slices[s], doc - base[s]

    def snippets(self, results: SearchResults,
                 length: int = 8) -> list[list[np.ndarray]]:
        """Decode the first ``length`` word ids of every hit document straight
        from the compressed index (no stored text).  Returns one list per
        query, one id array per hit (shorter docs come back whole)."""
        offs = jnp.arange(length, dtype=jnp.int32)
        out = []
        for b in range(len(results)):
            row = []
            for d, _score in results.hits(b):
                idx, local = self._local_index(d)
                n_take = min(length, int(np.asarray(idx.doc_len)[local]))
                lo = wtbc.doc_start(idx, jnp.int32(local))
                # fixed decode width (one compile per `length`, not per doc
                # length); positions clamped in-bounds, then trimmed on host
                ranks = np.asarray(jax.vmap(
                    lambda o: wtbc.decode_at(idx, jnp.minimum(lo + o, idx.n - 1))
                )(offs))[:n_take]
                row.append(np.asarray(self.model.word_of_rank)[ranks])
            out.append(row)
        return out

    def word_positions(self, doc: int, word_ids,
                       cap: int = 32) -> dict[int, np.ndarray]:
        """Doc-relative occurrence positions of each word id inside document
        ``doc`` (the first ``cap`` per word), extracted straight from the
        compressed index — the hit-highlighting companion to
        :meth:`snippets` (e.g. to mark every query word around a positional
        match)."""
        doc = int(doc)
        if not 0 <= doc < self.n_docs:
            raise ValueError(f"doc id {doc} outside [0, {self.n_docs})")
        idx, local = self._local_index(doc)
        V = self.model.vocab_size
        out = {}
        for w in word_ids:
            w = int(w)
            if not 1 <= w < V:
                raise ValueError(f"word id {w} outside [1, {V})")
            r = jnp.int32(self.model.rank_of_word[w])
            pos = np.asarray(positional.doc_positions(
                idx, r, jnp.int32(local), cap=cap))
            out[w] = pos[pos >= 0]
        return out

    # -- introspection -------------------------------------------------------

    @property
    def stats(self) -> dict:
        """Executor-cache occupancy and per-key jit trace counts (snapshotted
        under the same lock ``note()`` mutates under, so a reader never sees
        a dict mid-resize)."""
        with self._stats_lock:
            return {"executors": len(self._executors),
                    "traces": dict(self._trace_counts)}

    def space_report(self) -> dict[str, int]:
        """Index (and built-DRB) space, bytes per component."""
        report = wtbc.space_report(self.idx)
        aux = self._aux if self.backend == "single" else self._sharded.aux
        if aux is not None:
            aux_rep = drb.space_report(aux)
            report.update({f"drb_{k}": v for k, v in aux_rep.items()})
            report["total"] += sum(aux_rep.values())
        return report


_CTOR_TOKEN = object()
