"""Document-sharded distributed retrieval (DESIGN.md §4).

The paper's own deployment motivation is "a cluster that implements a large
in-memory distributed index".  We realize it the way production engines do —
document partitioning:

* the (s,c)-DC model is fitted once on **global** frequencies (codewords must
  agree across shards),
* each device along the sharding mesh axes holds a full WTBC over its own
  contiguous document range (shapes padded to the max shard so the stacked
  index is one rectangular pytree),
* a query is replicated, solved locally with the *identical* single-host
  kernels (`topk_dr` / `topk_drb_*`), and per-shard top-k lists are merged
  with one ``all_gather`` of (k,) floats+ints per shard followed by a local
  ``lax.top_k`` — the only cross-shard communication in the system.

Scoring uses the **global** idf table (replicated, V floats) so shard results
are directly comparable; per-shard `df` remains local (it drives DRB cursor
initialization only).

Straggler mitigation hook: `topk_dr` is an any-time algorithm — the
``max_pops`` budget bounds per-shard work; a budget-limited shard returns its
current best list and the merge remains correct for all documents examined
(EXPERIMENTS.md §Perf quantifies the exactness/latency trade).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import drb as drb_mod
from repro.core import ranked, scdc, wtbc
from repro.core.drb import DRBAux
from repro.core.wtbc import WTBCIndex


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("idx", "aux", "doc_base", "global_df", "global_idf",
                 "global_avg_dl"),
    meta_fields=("n_shards",))
@dataclasses.dataclass(frozen=True)
class ShardedWTBC:
    """Stacked (leading shard axis) per-shard indexes + global scoring tables."""
    idx: WTBCIndex          # every leaf has leading dim n_shards
    aux: DRBAux | None      # stacked DRB bitmaps (or None)
    doc_base: jnp.ndarray   # (n_shards,) int32 global docid of shard's doc 0
    global_df: jnp.ndarray  # (V,) int32 global document frequency per rank
    global_idf: jnp.ndarray # (V,) float32 (tf-idf form; other measures can
                            # derive their own table from global_df)
    global_avg_dl: jnp.ndarray  # () float32 (BM25 length normalization)
    n_shards: int


def _pad_to(a: np.ndarray, n: int, fill) -> np.ndarray:
    if a.shape[0] == n:
        return a
    pad = np.full((n - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


def _stack_bytemaps(maps) -> "wtbc.ByteMap":
    from repro.core.bytemap import ByteMap
    max_data = max(m.data.shape[0] for m in maps)
    max_blocks = max(m.counts.shape[0] for m in maps)
    datas, counts, lengths = [], [], []
    for m in maps:
        d = np.asarray(m.data)
        c = np.asarray(m.counts)
        datas.append(_pad_to(d, max_data, 0))
        # pad counter rows by repeating the final cumulative row: select's
        # binary search stays correct past the logical end
        if c.shape[0] < max_blocks:
            c = np.concatenate([c, np.repeat(c[-1:], max_blocks - c.shape[0], axis=0)])
        counts.append(c)
        lengths.append(np.asarray(m.length))
    return ByteMap(data=np.stack(datas), counts=np.stack(counts),
                   length=np.stack(lengths), block=maps[0].block)


def build_sharded(doc_tokens: list[np.ndarray], vocab_size: int, n_shards: int,
                  block: int = 4096, with_drb: bool = True,
                  eps: float = 1e-6) -> tuple[ShardedWTBC, scdc.SCDCModel]:
    """Fit global codes, build + stack per-shard indexes (host side; the
    leaves are host arrays until :func:`place` puts them on a mesh)."""
    n_docs = len(doc_tokens)
    doc_len = np.array([len(d) for d in doc_tokens], dtype=np.int64)
    flat = np.concatenate([np.concatenate([d, [0]]) for d in doc_tokens])
    freqs = np.bincount(flat, minlength=vocab_size)
    model = scdc.fit(freqs, reserve_first=0)

    # contiguous document ranges, balanced by token count
    tokens_cum = np.cumsum(doc_len + 1)
    targets = (np.arange(1, n_shards) * tokens_cum[-1]) // n_shards
    cuts = np.searchsorted(tokens_cum, targets).tolist()
    bounds = [0] + [c + 1 for c in cuts] + [n_docs]
    bounds = sorted(set(bounds))
    while len(bounds) < n_shards + 1:          # degenerate tiny corpora
        bounds.append(n_docs)
    shard_docs = [doc_tokens[bounds[i]:bounds[i + 1]] for i in range(n_shards)]
    for sd in shard_docs:
        if not sd:
            raise ValueError("a shard received zero documents; lower n_shards")

    # global document frequencies -> global idf and global stopword decision
    df_global = np.zeros(vocab_size, dtype=np.int64)
    for sd in shard_docs:
        for d in sd:
            df_global[np.unique(model.rank_of_word[d])] += 1
    idf_np = np.log(n_docs / np.maximum(df_global, 1)).astype(np.float32)
    idf_np[wtbc.SEP_RANK] = 0.0
    has_bm_global = (idf_np >= eps) & (df_global > 0)

    shards = [wtbc.build_index_with_model(sd, model, block) for sd in shard_docs]
    auxes = ([drb_mod.build_aux(s, model, sd, eps, has_bm_override=has_bm_global)
              for s, sd in zip(shards, shard_docs)]
             if with_drb else None)
    doc_base = np.asarray(bounds[:-1], dtype=np.int32)

    # --- stack index leaves, padding ragged dimensions ------------------------
    max_docs = max(int(s.n_docs) for s in shards)
    levels = tuple(_stack_bytemaps([s.levels[L] for s in shards])
                   for L in range(wtbc.MAX_LEVELS))
    offsets = tuple(np.stack([np.asarray(s.offsets[L]) for s in shards])
                    for L in range(wtbc.MAX_LEVELS))

    def stk(get, pad_fill=None, pad_len=None):
        arrs = [np.asarray(get(s)) for s in shards]
        if pad_len is not None:
            arrs = [_pad_to(a, pad_len, pad_fill) for a in arrs]
        return np.stack(arrs)

    big_n = int(max(int(s.n) for s in shards))
    idx = WTBCIndex(
        levels=levels, offsets=offsets,
        cw=stk(lambda s: s.cw), cw_len=stk(lambda s: s.cw_len),
        node_off=stk(lambda s: s.node_off), base_rank=stk(lambda s: s.base_rank),
        sep_pos=stk(lambda s: s.sep_pos, pad_fill=big_n, pad_len=max_docs),
        df=stk(lambda s: s.df), occ=stk(lambda s: s.occ),
        doc_len=stk(lambda s: s.doc_len, pad_fill=0, pad_len=max_docs),
        n=stk(lambda s: s.n), n_docs=stk(lambda s: s.n_docs),
        s=model.s, c=model.c)

    aux = None
    if with_drb:
        from repro.core.bitvec import BitVec
        max_words = max(a.bv.words.shape[0] for a in auxes)
        max_blocks = max(a.bv.counts.shape[0] for a in auxes)
        words_, counts_, nbits_, offs_, hasbm_ = [], [], [], [], []
        for a in auxes:
            w = _pad_to(np.asarray(a.bv.words), max_words, 0)
            c_ = np.asarray(a.bv.counts)
            if c_.shape[0] < max_blocks:
                c_ = np.concatenate([c_, np.repeat(c_[-1:], max_blocks - c_.shape[0], axis=0)])
            words_.append(w); counts_.append(c_)
            nbits_.append(np.asarray(a.bv.n_bits))
            offs_.append(np.asarray(a.bit_off)); hasbm_.append(np.asarray(a.has_bm))
        aux = DRBAux(
            bv=BitVec(words=np.stack(words_), counts=np.stack(counts_),
                      n_bits=np.stack(nbits_)),
            bit_off=np.stack(offs_), has_bm=np.stack(hasbm_), eps=eps)

    avg_dl = np.float32(doc_len.sum() / max(n_docs, 1))
    sharded = ShardedWTBC(idx=idx, aux=aux, doc_base=doc_base,
                          global_df=df_global.astype(np.int32),
                          global_idf=idf_np, global_avg_dl=avg_dl,
                          n_shards=n_shards)
    return sharded, model


def place(sharded: ShardedWTBC, mesh: Mesh,
          shard_axes: str | tuple[str, ...]) -> ShardedWTBC:
    """Put a (host-built or restored) sharded index on ``mesh``: every
    stacked leaf split along ``shard_axes`` so each device holds only its own
    shard, the global scoring tables replicated — the layout
    :func:`distributed_topk`'s ``shard_map`` consumes, so no call reshards."""
    axes = (shard_axes,) if isinstance(shard_axes, str) else tuple(shard_axes)
    split = NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))
    whole = NamedSharding(mesh, P())
    put = lambda sharding: (lambda x: jax.device_put(x, sharding))
    return dataclasses.replace(
        sharded,
        idx=jax.tree.map(put(split), sharded.idx),
        aux=(jax.tree.map(put(split), sharded.aux)
             if sharded.aux is not None else None),
        doc_base=jax.device_put(sharded.doc_base, split),
        global_df=jax.device_put(sharded.global_df, whole),
        global_idf=jax.device_put(sharded.global_idf, whole),
        global_avg_dl=jax.device_put(sharded.global_avg_dl, whole))


# ---------------------------------------------------------------------------
# distributed query (shard_map + all_gather merge)
# ---------------------------------------------------------------------------

def distributed_topk(sharded: ShardedWTBC, words: jnp.ndarray, wmask: jnp.ndarray,
                     *, k: int, method: str, mesh: Mesh,
                     shard_axes: str | tuple[str, ...],
                     heap_cap: int | None = None,
                     max_df_cap: int = 256,
                     max_pops: int | None = None,
                     measure=None,
                     idf: jnp.ndarray | None = None,
                     beam_width: int = 1) -> ranked.DRResult:
    """Run a top-k query over the sharded index under ``mesh``.

    method: 'dr-and' | 'dr-or' | 'drb-and' | 'drb-or'.
    shard_axes: mesh axis (or axes tuple) the documents are sharded over; the
    total device count along them must equal ``sharded.n_shards``.
    max_pops: per-shard any-time budget for the loop cores (DR and DRB-AND;
    straggler mitigation, see module docstring); None = run each shard to
    completion.  The merged result carries global anytime metadata
    (DESIGN.md §11): the global pending bound is the max over the shards'
    bounds, and a merged slot is certified iff its score *strictly* beats
    that bound — strict because a score tie across shards could hide a
    lower-doc-id tie winner behind another shard's frontier (conservative:
    a certified-at-a-tie local slot may come back uncertified merged).
    idf: (V,) replicated scoring table; defaults to ``sharded.global_idf``
    (tf-idf form).  Pass a measure-specific table (derivable from
    ``sharded.global_df``) so shard scores match the single-host backend.
    beam_width: per-shard frontier width for the DR / DRB-AND loop cores
    (DESIGN.md §6); each shard runs the identical beam the single-host
    backend would.
    """
    from repro.core import scoring
    measure = measure or scoring.TfIdf()
    axes = (shard_axes,) if isinstance(shard_axes, str) else tuple(shard_axes)
    if heap_cap is None:
        heap_cap = 2 * int(np.max(np.asarray(sharded.idx.n_docs))) + 4
    if idf is None:
        idf = sharded.global_idf

    spec_shard = P(axes if len(axes) > 1 else axes[0])
    sharded_specs = ShardedWTBC(
        idx=jax.tree.map(lambda _: spec_shard, sharded.idx),
        aux=(jax.tree.map(lambda _: spec_shard, sharded.aux)
             if sharded.aux is not None else None),
        doc_base=spec_shard,
        global_df=P(),                # replicated scoring tables
        global_idf=P(),
        global_avg_dl=P(),
        n_shards=sharded.n_shards)
    in_specs = (sharded_specs, P(), P(), P())
    # every method threads `padded` through the merge so the serving/obs
    # layer sees the same diagnostics sharded as single-host
    out_specs = (P(),) * 9

    def local(sh: ShardedWTBC, words, wmask, idf_tab):
        batched = words.ndim == 2                      # (B, Q) query batches
        idx = jax.tree.map(lambda x: x[0], sh.idx)

        def one(words1, wmask1):
            if method == "dr-and" or method == "dr-or":
                return ranked.topk_dr(idx, words1, wmask1, idf_tab,
                                      k=k, conjunctive=(method == "dr-and"),
                                      heap_cap=heap_cap, max_pops=max_pops,
                                      beam_width=beam_width)
            aux = jax.tree.map(lambda x: x[0], sh.aux)
            if method == "drb-and":
                return drb_mod.topk_drb_and(idx, aux, words1, wmask1, measure,
                                            k=k, idf=idf_tab,
                                            avg_dl=sh.global_avg_dl,
                                            beam_width=beam_width,
                                            max_pops=max_pops)
            if method == "drb-or":
                return drb_mod.topk_drb_or(idx, aux, words1, wmask1, measure,
                                           k=k, max_df_cap=max_df_cap,
                                           idf=idf_tab,
                                           avg_dl=sh.global_avg_dl)
            raise ValueError(method)

        if batched:
            if method in ("dr-and", "dr-or"):
                # the explicitly batched core, NOT vmap(one): under vmap the
                # active-frontier lax.switch index is batched, which executes
                # EVERY bucket body per trip and selects; topk_dr_batch
                # hoists a scalar dispatch above the vmapped row body
                # (bitwise-equal leaves — see core/ranked.py)
                res = ranked.topk_dr_batch(
                    idx, words, wmask, idf_tab, k=k,
                    conjunctive=(method == "dr-and"), heap_cap=heap_cap,
                    max_pops=max_pops, beam_width=beam_width)
            else:
                res = jax.vmap(one)(words, wmask)     # leaves (B, k)
        else:
            res = one(words, wmask)
        gdocs = jnp.where(res.docs >= 0, res.docs + sh.doc_base[0], -1)
        all_d, all_s = gdocs, res.scores               # (B?, k)
        for ax in axes:
            # gather shard axis then fold it into the candidate axis
            all_d = jnp.moveaxis(jax.lax.all_gather(all_d, ax), 0, -2)
            all_s = jnp.moveaxis(jax.lax.all_gather(all_s, ax), 0, -2)
            all_d = all_d.reshape(*all_d.shape[:-2], -1)
            all_s = all_s.reshape(*all_s.shape[:-2], -1)
        # (k+1)-wide merge: slot k's score is the best candidate the merge
        # DROPS — a known document not in the result, folded into the
        # reported bound below.  top_k tie-breaks toward the earliest
        # gathered index = the smallest global doc id (shard blocks are
        # doc-ordered and so is each shard's list), matching the
        # single-host tie order, so a dropped tie-loser always ranks after
        # every retained slot.
        kk = min(k + 1, all_s.shape[-1])
        top_s, ti = jax.lax.top_k(all_s, kk)
        dropped_s = (top_s[..., k] if kk > k
                     else jnp.full(top_s.shape[:-1], -jnp.inf, jnp.float32))
        top_s, ti = top_s[..., :k], ti[..., :k]
        top_d = jnp.take_along_axis(all_d, ti, axis=-1)
        n_found = jnp.sum(top_s > -jnp.inf, axis=-1).astype(jnp.int32)
        # work metrics sum over shards; overflow is any-shard; the pending
        # bound is max-over-shards (a hidden doc on any shard is bounded by
        # its own shard's pending threshold)
        iters, pops, over = res.iters, res.pops, res.overflowed.astype(jnp.int32)
        padded, bound = res.padded, res.bound
        for ax in axes:
            iters = jax.lax.psum(iters, ax)
            pops = jax.lax.psum(pops, ax)
            over = jax.lax.psum(over, ax)
            bound = jax.lax.pmax(bound, ax)
            padded = jax.lax.psum(padded, ax)
        # certification is strict-score vs the global *pending* bound (see
        # the docstring); the reported bound additionally covers the docs
        # the merge itself dropped
        certified = ((top_s > bound[..., None])
                     & ~(over > 0)[..., None] & (top_s > -jnp.inf))
        bound_out = jnp.maximum(bound, dropped_s)
        out = (jnp.where(top_s > -jnp.inf, top_d, -1), top_s, n_found, iters,
               pops, over > 0, certified, bound_out)
        return out + (padded,)

    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    res = fn(sharded, words, wmask, idf)
    docs, scores, n_found, iters, pops, over, certified, bound, padded = res
    return ranked.DRResult(docs, scores, n_found, iters, pops, over,
                           padded=padded, certified=certified, bound=bound)
