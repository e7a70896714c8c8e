"""Batched locate: the ``wavelet_locate`` kernel (TPU body under the Pallas
interpreter), the vmapped-walk oracle and the scalar ``wtbc.locate`` against
the token stream itself.

The index uses an (s,c)-DC code with s = 2, so its words have codewords of
1, 2 and 3 bytes, and blocks of 128 or 512 bytes, so every level spans
several counter blocks: locating every token covers first and last
occurrences and both sides of every block boundary on every level.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scdc, wtbc
from repro.kernels import backend, ops
from repro.kernels import wavelet_locate as wl


@functools.lru_cache(maxsize=None)
def deep_index(block: int, n_docs: int = 90, vocab: int = 1400, seed: int = 5):
    """(index, token ranks, j of each token) for a random collection whose
    code has s = 2 stoppers."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(1, vocab, rng.integers(20, 80)) for _ in range(n_docs)]
    flat = np.concatenate([np.append(d, 0) for d in docs])
    freqs = np.bincount(flat, minlength=vocab)
    order = np.argsort(-freqs, kind="stable").astype(np.int32)
    order = np.concatenate(([0], order[order != 0])).astype(np.int32)
    rank_of_word = np.empty_like(order)
    rank_of_word[order] = np.arange(vocab, dtype=np.int32)
    codes, lens = scdc.encode_table(2, vocab)
    model = scdc.SCDCModel(s=2, c=254, codes=codes, lens=lens,
                           rank_of_word=rank_of_word, word_of_rank=order,
                           freqs=freqs[order])
    idx = wtbc.build_index_with_model(docs, model, block=block)
    ranks = rank_of_word[flat]
    # j of each token: its 1-based occurrence number among its word's
    order_tok = np.argsort(ranks, kind="stable")
    first = np.searchsorted(ranks[order_tok], ranks[order_tok])
    js = np.empty(len(ranks), np.int64)
    js[order_tok] = np.arange(len(ranks)) - first + 1
    return idx, ranks, js


def dead_pairs(idx, rng, m):
    """``m`` dead (word, j) pairs: j = 0, j < 0, j = occ + 1, j huge."""
    occ = np.asarray(idx.occ)
    w = rng.integers(0, idx.vocab_size, m)
    j = np.choose(rng.integers(0, 4, m),
                  [np.zeros(m), -np.ones(m) * 3, occ[w] + 1,
                   np.full(m, 2**30)]).astype(np.int64)
    return w, j


def run_path(path, idx, words, js):
    w = jnp.asarray(words, jnp.int32)
    j = jnp.asarray(js, jnp.int32)
    if path == "kernel":
        with backend.force_plan("tpu:interpret"):
            return np.asarray(wtbc.locate_batch(idx, w, j))
    if path == "oracle":
        with ops.use_kernels(False):
            return np.asarray(wtbc.locate_batch(idx, w, j))
    return np.asarray(jax.vmap(lambda a, b: wtbc.locate(idx, a, b))(w, j))


@pytest.mark.parametrize("path", ["kernel", "oracle", "vmap"])
@pytest.mark.parametrize("block", [128, 512])
def test_locate_every_occurrence(block, path):
    idx, ranks, js = deep_index(block)
    lens = np.asarray(idx.cw_len)[ranks]
    assert set(np.unique(lens)) == {1, 2, 3}
    assert all(lv.counts.shape[0] - 1 >= 3 for lv in idx.levels)
    rng = np.random.default_rng(block)
    dw, dj = dead_pairs(idx, rng, 200)
    words = np.concatenate([ranks, dw])
    jj = np.concatenate([js, dj])
    got = run_path(path, idx, words, jj)
    n = len(ranks)
    np.testing.assert_array_equal(got[:n], np.arange(n))
    np.testing.assert_array_equal(got[n:], int(idx.n))


@pytest.mark.parametrize("m", [1000, 1024, 1025, 2500])
def test_locate_chunks(m):
    """M below, at and above the kernel's chunk of pairs, and not a multiple
    of it: kernel and oracle agree on every pair, live or dead."""
    idx, ranks, js = deep_index(128)
    rng = np.random.default_rng(m)
    pick = rng.integers(0, len(ranks), m)
    dw, dj = dead_pairs(idx, rng, m)
    dead = rng.random(m) < 0.4
    words = np.where(dead, dw, ranks[pick])
    jj = np.where(dead, dj, js[pick])
    got = run_path("kernel", idx, words, jj)
    np.testing.assert_array_equal(got, run_path("oracle", idx, words, jj))
    np.testing.assert_array_equal(got[~dead], pick[~dead])
    np.testing.assert_array_equal(got[dead], int(idx.n))


def test_locate_under_vmap_is_one_launch():
    """The executors vmap their row bodies: the batching rule flattens the
    (B, M) pairs into one launch, with the per-row answers."""
    idx, ranks, js = deep_index(512)
    rng = np.random.default_rng(3)
    pick = rng.integers(0, len(ranks), (3, 700))
    words = jnp.asarray(ranks[pick], jnp.int32)
    jj = jnp.asarray(js[pick], jnp.int32).at[1, :50].set(0)
    fn = jax.vmap(lambda w, j: wtbc.locate_batch(idx, w, j))
    with backend.force_plan("tpu:interpret"):
        got = np.asarray(fn(words, jj))
        jaxpr = str(jax.make_jaxpr(fn)(words, jj))
    assert jaxpr.count("pallas_call") == 1
    want = np.where(np.asarray(jj) == 0, int(idx.n), pick)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("plan,kernel", [("tpu:interpret", True),
                                         ("gpu:interpret", False),
                                         ("ref", False)])
def test_locate_dispatch(plan, kernel):
    """The kernel runs under a tpu plan only; there is no Triton lowering,
    so every other plan runs the oracle."""
    idx, ranks, js = deep_index(512)
    w = jnp.asarray(ranks[:64], jnp.int32)
    j = jnp.asarray(js[:64], jnp.int32)
    with backend.force_plan(plan):
        jaxpr = str(jax.make_jaxpr(
            lambda a, b: wtbc.locate_batch(idx, a, b))(w, j))
        got = np.asarray(wtbc.locate_batch(idx, w, j))
    assert ("pallas_call" in jaxpr) == kernel
    np.testing.assert_array_equal(got, np.arange(64))


def test_locate_level_counters_out_of_vmem():
    """A level whose counter matrix exceeds the VMEM budget is searched by
    DMAing one 8-row counter group per probe; answers are the same."""
    idx, ranks, js = deep_index(128, n_docs=2600)
    rows = [lv.counts.shape[0] for lv in idx.levels]
    assert not wl._resident(rows[0] + -rows[0] % 8)
    assert wl._resident(rows[2] + -rows[2] % 8)
    rng = np.random.default_rng(11)
    blk0 = np.arange(1, rows[0] - 1) * 128          # level-0 block starts
    pick = np.unique(np.concatenate([blk0 - 1, blk0, [0, len(ranks) - 1],
                                     rng.integers(0, len(ranks), 1500)]))
    got = run_path("kernel", idx, ranks[pick], js[pick])
    np.testing.assert_array_equal(got, pick)
