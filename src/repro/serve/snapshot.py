"""Versioned on-disk snapshots of a built :class:`repro.engine.SearchEngine`.

The paper's system premise is that the compressed index IS the only thing
kept — so a server must be able to start from it directly instead of
re-deriving it from the raw corpus on every boot (which would both cost
minutes and require keeping the text the paper says we don't store).  A
snapshot persists everything a query needs:

    WTBCIndex (or the stacked ShardedWTBC)  — the compressed self-index
    DRBAux                                  — tf bitmaps, when built
    SCDCModel arrays                        — word-id <-> rank + codewords
    EngineConfig + structural metadata      — to reassemble the exact engine

Array payloads ride the crash-safe ``repro.checkpoint.ckpt`` machinery
(write-to-tmp, fsync'd manifest, atomic rename, per-leaf CRC32s) in its
``fmt="npy"`` layout: one raw ``.npy`` per leaf, so ``load`` memory-maps
them — the arrays alias the snapshot files and nothing is materialized until
first touch.  On the CPU backend even device placement is zero-copy:
``jax.device_put`` aliases the 64-byte-aligned mmap'd pages directly (see
``_device_put``), so a server boots in O(metadata), not O(index).  Structure
(tuple arities, static ``(s, c)``, per-level block sizes, backend) travels in
the manifest's ``user_meta``; ``load`` rebuilds a skeleton pytree from it and
lets ``ckpt.restore`` fill in the leaves by name.

    snapshot.save(engine, "snap/")            # -> version 1
    engine = snapshot.load("snap/")           # newest version, no corpus

Versions are monotonically increasing integers (one directory each), so a
serving fleet can roll forward/back by pointing at a version; ``save`` never
mutates a committed version in place.
"""
from __future__ import annotations

import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import bitvec, bytemap, distributed, drb, scdc, wtbc
from repro.engine import EngineConfig
from repro.engine.facade import SearchEngine

SNAPSHOT_FORMAT = 1


# ---------------------------------------------------------------------------
# save
# ---------------------------------------------------------------------------

def _structure_meta(engine: SearchEngine) -> dict:
    idx = engine.idx
    aux = engine._aux if engine.backend == "single" else engine._sharded.aux
    meta = {
        "snapshot_format": SNAPSHOT_FORMAT,
        "backend": engine.backend,
        "n_docs": int(engine.n_docs),
        "config": dataclasses.asdict(engine.config),
        "model": {"s": engine.model.s, "c": engine.model.c},
        "index": {"s": idx.s, "c": idx.c,
                  "blocks": [l.block for l in idx.levels],
                  "n_levels": len(idx.levels)},
        "has_aux": aux is not None,
        "aux_eps": None if aux is None else aux.eps,
    }
    if engine.backend == "sharded":
        ax = engine._shard_axes
        meta["n_shards"] = engine._sharded.n_shards
        meta["shard_axes"] = list(ax) if isinstance(ax, tuple) else ax
    return meta


def save(engine: SearchEngine, snap_dir: str | pathlib.Path,
         version: int | None = None) -> pathlib.Path:
    """Persist ``engine`` as a new snapshot version (committed atomically).

    A ``with_drb=True`` single-host engine gets its DRB bitmaps built first —
    the snapshot must be self-contained (no raw tokens survive a load, so a
    lazy build afterwards would be impossible).
    """
    snap_dir = pathlib.Path(snap_dir)
    if version is None:
        existing = ckpt.list_steps(snap_dir)
        version = (existing[-1] + 1) if existing else 1
    if engine.backend == "single":
        if engine.config.with_drb:
            engine.aux                        # force the lazy bitmap build
        state = {"idx": engine._idx, "aux": engine._aux,
                 "model": _model_arrays(engine.model)}
    else:
        state = {"sharded": engine._sharded,
                 "model": _model_arrays(engine.model)}
    return ckpt.save(snap_dir, version, state, fmt="npy",
                     meta=_structure_meta(engine))


def _model_arrays(model: scdc.SCDCModel) -> dict:
    return {"codes": model.codes, "lens": model.lens,
            "rank_of_word": model.rank_of_word,
            "word_of_rank": model.word_of_rank, "freqs": model.freqs}


# ---------------------------------------------------------------------------
# skeletons — correct treedef, dummy leaves; ckpt.restore swaps leaves by name
# ---------------------------------------------------------------------------

_Z = np.zeros(0)


def _skel_bytemap(block: int) -> bytemap.ByteMap:
    return bytemap.ByteMap(data=_Z, counts=_Z, length=_Z, block=block)


def _skel_index(meta: dict) -> wtbc.WTBCIndex:
    im = meta["index"]
    return wtbc.WTBCIndex(
        levels=tuple(_skel_bytemap(b) for b in im["blocks"]),
        offsets=tuple(_Z for _ in im["blocks"]),
        cw=_Z, cw_len=_Z, node_off=_Z, base_rank=_Z, sep_pos=_Z,
        df=_Z, occ=_Z, doc_len=_Z, n=_Z, n_docs=_Z,
        s=im["s"], c=im["c"])


def _skel_aux(meta: dict) -> drb.DRBAux | None:
    if not meta["has_aux"]:
        return None
    return drb.DRBAux(bv=bitvec.BitVec(words=_Z, counts=_Z, n_bits=_Z),
                      bit_off=_Z, has_bm=_Z, eps=meta["aux_eps"])


def _skel_state(meta: dict) -> dict:
    model = {k: _Z for k in ("codes", "lens", "rank_of_word",
                             "word_of_rank", "freqs")}
    if meta["backend"] == "single":
        return {"idx": _skel_index(meta), "aux": _skel_aux(meta),
                "model": model}
    return {"sharded": distributed.ShardedWTBC(
                idx=_skel_index(meta), aux=_skel_aux(meta),
                doc_base=_Z, global_df=_Z, global_idf=_Z, global_avg_dl=_Z,
                n_shards=meta["n_shards"]),
            "model": model}


# ---------------------------------------------------------------------------
# load
# ---------------------------------------------------------------------------

def list_versions(snap_dir: str | pathlib.Path) -> list[int]:
    """Committed snapshot versions, oldest first."""
    return ckpt.list_steps(snap_dir)


def load(snap_dir: str | pathlib.Path, version: int | None = None, *,
         verify: bool = True, mmap: bool = True,
         mesh=None) -> SearchEngine:
    """Reassemble a ready-to-query engine from a snapshot (newest version by
    default) — no corpus, no index build, no bitmap build.

    verify: CRC-check every leaf against the manifest (reads all pages; pass
            ``False`` for the lazy fastest start).
    mmap:   memory-map the arrays instead of reading them eagerly.
    mesh:   sharded snapshots only — the mesh to place shards on (each
            device receives only its own shard); defaults to a fresh 1-D mesh
            over the first ``n_shards`` local devices, like
            ``SearchEngine.shard`` builds.
    """
    manifest, version = ckpt.read_manifest(snap_dir, version)
    meta = manifest.get("user_meta") or {}
    fmt = meta.get("snapshot_format")
    if fmt != SNAPSHOT_FORMAT:
        raise ValueError(f"snapshot format {fmt!r} not supported "
                         f"(this build reads format {SNAPSHOT_FORMAT})")
    state, _ = ckpt.restore(snap_dir, _skel_state(meta), step=version,
                            verify_crc=verify, mmap=mmap)
    config = EngineConfig(**meta["config"])
    model = scdc.SCDCModel(s=meta["model"]["s"], c=meta["model"]["c"],
                           **state["model"])
    if meta["backend"] == "single":
        idx = _device_put(state["idx"])
        aux = _device_put(state["aux"]) if meta["has_aux"] else None
        return SearchEngine._restore(config=config, model=model,
                                     n_docs=meta["n_docs"], backend="single",
                                     idx=idx, aux=aux)
    axes = meta["shard_axes"]
    shard_axes = tuple(axes) if isinstance(axes, list) else axes
    if mesh is None:
        n_shards = meta["n_shards"]
        devices = jax.devices()
        if len(devices) < n_shards:
            raise ValueError(f"snapshot needs {n_shards} devices, have "
                             f"{len(devices)}; pass a mesh")
        names = shard_axes if isinstance(shard_axes, tuple) else (shard_axes,)
        if len(names) != 1:
            raise ValueError("multi-axis sharded snapshots need an explicit "
                             "mesh")
        mesh = jax.sharding.Mesh(
            np.array(devices[:n_shards]).reshape(n_shards), names)
    sharded = distributed.place(state["sharded"], mesh, shard_axes)
    return SearchEngine._restore(config=config, model=model,
                                 n_docs=meta["n_docs"], backend="sharded",
                                 sharded=sharded, mesh=mesh,
                                 shard_axes=shard_axes)


def _device_put(tree):
    """Host arrays -> device arrays.

    On the CPU backend ``jax.device_put`` *aliases* host buffers that are
    64-byte aligned instead of copying — and ``.npy`` array payloads are
    64-byte aligned by format (header padding), so the mmap'd, read-only
    snapshot leaves become device arrays **zero-copy**: boot touches no
    data pages until a query faults them in (tests/test_mega.py pins the
    aliasing via ``unsafe_buffer_pointer``).  Dtype canonicalization
    (int64 -> int32 under the default x64 setting) matches ``jnp.asarray``
    exactly, so results are identical either way; non-CPU backends pay the
    one unavoidable host->device copy."""
    return jax.tree.map(jax.device_put, tree)
