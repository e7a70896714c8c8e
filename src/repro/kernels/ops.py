"""Public wrappers for the Pallas kernels, with lowering dispatch.

Lowering policy lives in ``kernels/backend.py`` (DESIGN.md §9): each wrapper
asks for a plan and runs either a compiled kernel (TPU or Triton), the kernel
body under the Pallas interpreter (only when explicitly requested), or the
pure-jnp oracle.  ``descent_plan()`` governs the descent family — the
Algorithm-1 hot path — honouring ``force_plan`` / ``REPRO_KERNEL_BACKEND``;
the standalone TPU-only ops (byte_rank, bitmap_rank1, segment_tf) compile on
TPU and fall back to the oracle elsewhere (their scalar-prefetch pipelines
have no Triton lowering, and their sequential interpret-mode grids are
strictly slower than the vectorized oracle).

`use_kernels(False)` forces the oracle path everywhere (benchmark A/B
switch and the parity tests' reference arm).
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from repro.core.bitvec import BitVec
from repro.core.bytemap import ByteMap
from repro.kernels import backend
from repro.kernels import byte_rank as _byte_rank_k
from repro.kernels import bitmap_rank as _bitmap_rank_k
from repro.kernels import topk_score as _topk_score_k
from repro.kernels import wavelet_descent as _wavelet_descent_k
from repro.kernels import wavelet_locate as _wavelet_locate_k
from repro.kernels import ref

_STATE = {"enabled": True}


@contextlib.contextmanager
def use_kernels(enabled: bool):
    prev = _STATE["enabled"]
    _STATE["enabled"] = enabled
    try:
        yield
    finally:
        _STATE["enabled"] = prev


def _standalone_kernel() -> bool:
    """Kernel-vs-oracle choice for the standalone TPU-only ops: compiled
    kernel on TPU, kernel under interpret only when a force/env explicitly
    asks for an interpret plan, oracle otherwise."""
    if not _STATE["enabled"]:
        return False
    plan = backend.descent_plan()
    if plan.kind == "tpu":
        return True
    return plan.interpret     # an explicit *:interpret request exercises them


def rank_batch(bm: ByteMap, bytes_q: jnp.ndarray, pos_q: jnp.ndarray) -> jnp.ndarray:
    """Batched bytemap rank — kernel on TPU / oracle elsewhere."""
    if _standalone_kernel():
        return _byte_rank_k.byte_rank(bm.data, bm.counts, bm.length,
                                      bytes_q, pos_q, block=bm.block)
    return ref.byte_rank_ref(bm.data, bm.counts, bm.length, bytes_q, pos_q,
                             block=bm.block)


def bitmap_rank1_batch(bv: BitVec, pos_q: jnp.ndarray) -> jnp.ndarray:
    if _standalone_kernel():
        return _bitmap_rank_k.bitmap_rank1(bv.words, bv.counts, bv.n_bits,
                                           pos_q)
    return ref.bitmap_rank1_ref(bv.words, bv.counts, bv.n_bits, pos_q)


def scored_topk(cands: jnp.ndarray, query: jnp.ndarray, *, k: int,
                tile: int = 1024) -> tuple[jnp.ndarray, jnp.ndarray]:
    if _standalone_kernel():
        return _topk_score_k.scored_topk(cands, query, k=k, tile=tile)
    return ref.scored_topk_ref(cands, query, k=k)


def wavelet_count_batch(levels, cw, cw_len, node_off, base_rank,
                        words, los, his) -> jnp.ndarray:
    """Batched fused 3-level WTBC count (the Algorithm-1 hot path).

    Dispatch via ``backend.descent_plan()``:

    * ``tpu`` / ``gpu`` — ONE ``wavelet_descent`` launch (DMA-gather or
      Triton ``plgpu.load``-gather lowering) for the whole (M × levels × 2)
      rank workload;
    * ``ref`` (no accelerator) — the pure-jnp batched descent, one
      vectorized rank batch per level.  The interpret-mode kernel iterates
      its grid sequentially, which inside the beam search's ``while_loop``
      is strictly slower than the vectorized oracle, so interpret runs only
      when a force/env explicitly asks for it (parity tests, the CI
      gpu-lowering job).
    """
    plan = backend.descent_plan() if _STATE["enabled"] else None
    if plan is not None and plan.kind in backend.ACCELERATORS:
        return _wavelet_descent_k.wavelet_descent(
            levels, cw, cw_len, node_off, base_rank, words, los, his,
            block=levels[0].block, lowering=plan.tag)
    return ref.wavelet_count_ref(levels, cw, cw_len, node_off, base_rank,
                                 words, los, his)


def wavelet_locate_batch(levels, cw, cw_len, node_off, base_rank, occ, n,
                         words, js) -> jnp.ndarray:
    """Batched locate: root position of the ``js[i]``-th occurrence of
    ``words[i]``, ``n`` for a dead pair (j < 1 or j > occ).

    Under a ``tpu`` plan (``backend.descent_plan()``, ``tpu:interpret``
    included) ONE ``wavelet_locate`` launch, which skips dead pairs; there is
    no Triton lowering, so every other plan, and ``use_kernels(False)``, runs
    the vmapped walk (``ref.wavelet_locate_ref``)."""
    plan = backend.descent_plan() if _STATE["enabled"] else None
    if plan is not None and plan.kind == "tpu":
        return _wavelet_locate_k.batched_locate(
            levels[0].block, plan.interpret)(
                levels, cw, cw_len, node_off, base_rank, occ, n, words, js)
    return ref.wavelet_locate_ref(levels, cw, cw_len, node_off, base_rank,
                                  occ, n, words, js)


def segment_tf_batch(bm: ByteMap, byte, bounds) -> "jnp.ndarray":
    """Per-segment tf of one byte over sorted boundaries (kernel on TPU)."""
    from repro.kernels import segment_tf as _seg
    if _standalone_kernel():
        return _seg.segment_tf(bm.data, bm.counts, bm.length, byte, bounds,
                               block=bm.block)
    r = ref.byte_rank_ref(bm.data, bm.counts, bm.length,
                          jnp.full(bounds.shape, byte, jnp.int32),
                          bounds, block=bm.block)
    return r[1:] - r[:-1]
