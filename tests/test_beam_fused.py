"""Device-resident beam iteration (ISSUE 8, DESIGN.md §9) — parity + wiring.

Four layers, mirroring the PR's pieces:

* **backend resolution** — the interpret-only-when-asked contract of
  ``kernels/backend.py``: auto-detection per host platform, the explicit >
  force > env > auto precedence, forced-accelerator-on-CPU degrading to the
  interpreter (how CI exercises the Triton path), and the regression that a
  kernel entry point called WITHOUT an interpret flag resolves it from the
  host instead of silently interpreting;
* **fused beam step** — a 210-case randomized A/B sweep (the test_mega case
  generator, on the DR slice where ``mega=True`` engages) pinning the fused
  single-launch beam iteration (``kernels/beam_step.py``, selected via
  ``force_plan("gpu:interpret")``) BITWISE against the jnp pool path —
  results *and* loop counters — plus the empty-range / conjunctive-miss and
  pool-overflow-latch edges.  The shared engine corpus spans ~9 counter
  blocks, so descents cross block boundaries throughout;
* **engine threading** — ``EngineConfig.kernel_backend`` routing, the
  ``ExecutorKey.lowering`` cache split (a forced plan never reuses a program
  compiled under another lowering), and config validation;
* **active-frontier buckets** — ``topk_dr_batch``'s scalar-dispatch bucketed
  loop is bitwise ``vmap(topk_dr)`` on every leaf at every width, P=1 never
  pads, and pad waste is surfaced through ``SearchResults.diagnostics``;
  plus the arithmetic of the WTBC query-path roofline model these counters
  feed (``analysis/roofline.py``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_mega import _sweep_cases

from repro.analysis import roofline
from repro.launch.mesh import CHIP_PEAKS
from repro.core import ranked
from repro.engine import EngineConfig, SearchEngine
from repro.kernels import backend, ops, ref
from repro.text import corpus


# ---------------------------------------------------------------------------
# backend resolution (the interpret-default fix)
# ---------------------------------------------------------------------------

def test_resolve_interpret_auto_detection(monkeypatch):
    """Explicit flags win; None resolves from the host platform — on an
    accelerator the kernel must COMPILE, never silently interpret."""
    assert backend.resolve_interpret(True) is True
    assert backend.resolve_interpret(False) is False
    assert backend.resolve_interpret(None) == (
        backend.canonical_backend() not in backend.ACCELERATORS)
    for platform, want in [("tpu", False), ("cuda", False), ("rocm", False),
                           ("cpu", True), ("METAL", True)]:
        monkeypatch.setattr(jax, "default_backend", lambda p=platform: p)
        assert backend.resolve_interpret(None) is want, platform
    monkeypatch.setattr(jax, "default_backend", lambda: "cuda")
    assert backend.canonical_backend() == "gpu"
    assert backend.accelerator() == "gpu"


def test_descent_plan_precedence(monkeypatch):
    auto = backend.descent_plan().tag
    assert auto in ("ref", "tpu", "gpu")
    monkeypatch.setenv(backend.ENV_VAR, "gpu:interpret")
    assert backend.descent_plan().tag == "gpu:interpret"      # env > auto
    with backend.force_plan("ref"):
        assert backend.descent_plan().tag == "ref"            # force > env
        assert backend.descent_plan("tpu:interpret").tag == "tpu:interpret"
    assert backend.descent_plan().tag == "gpu:interpret"      # force restored
    monkeypatch.delenv(backend.ENV_VAR)
    assert backend.descent_plan().tag == auto
    with pytest.raises(ValueError):
        backend.descent_plan("metal")
    with pytest.raises(ValueError):
        with backend.force_plan("bogus"):
            pass                                              # pragma: no cover


def test_forced_accelerator_degrades_to_interpret():
    """Forcing a lowering the host cannot compile runs its body under the
    Pallas interpreter — the CI gpu-lowering configuration."""
    if backend.accelerator():
        pytest.skip("host has a real accelerator")
    assert backend.descent_plan("gpu") == backend.KernelPlan("gpu", True)
    assert backend.descent_plan("tpu") == backend.KernelPlan("tpu", True)
    assert backend.descent_plan("auto").tag == "ref"
    # direct kernel calls cannot fall back to jnp: ref -> portable interpret
    assert backend.kernel_plan("ref").tag == "gpu:interpret"
    assert backend.kernel_plan(None).interpret is True
    assert backend.kernel_plan("gpu", interpret=False).interpret is False


def test_kernel_entry_interpret_defaults(small_index):
    """Regression (the old ``interpret=True`` defaults): entry points called
    with NO interpret flag resolve it from the host and still match the
    oracle — on this CPU host that means the interpreter, chosen by policy
    rather than by a hard-coded default."""
    from repro.core import bytemap
    from repro.kernels import byte_rank as brk
    from repro.kernels import wavelet_descent as wd

    idx, _ = small_index
    rng = np.random.default_rng(11)
    words = jnp.asarray(rng.integers(1, idx.vocab_size, 8), jnp.int32)
    lo = jnp.zeros(8, jnp.int32)
    hi = jnp.asarray(rng.integers(0, int(idx.n) + 1, 8), jnp.int32)
    got = wd.wavelet_descent(idx.levels, idx.cw, idx.cw_len, idx.node_off,
                             idx.base_rank, words, lo, hi,
                             block=idx.levels[0].block)   # no interpret arg
    want = ref.wavelet_count_ref(idx.levels, idx.cw, idx.cw_len,
                                 idx.node_off, idx.base_rank, words, lo, hi)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    data = rng.integers(0, 16, 3000).astype(np.uint8)
    bm = bytemap.build(data, block=512)
    bq = jnp.asarray(rng.integers(0, 16, 6), jnp.int32)
    pq = jnp.asarray(rng.integers(0, 3001, 6), jnp.int32)
    got = brk.byte_rank(bm.data, bm.counts, bm.length, bq, pq, block=512)
    want = ref.byte_rank_ref(bm.data, bm.counts, bm.length, bq, pq, block=512)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# fused beam step vs the jnp pool path — 210-case randomized A/B
# ---------------------------------------------------------------------------

FUSED_MODES = ("and", "or")
FUSED_CASES_PER_MODE = 105          # 2 x 105 = 210 (ISSUE floor: 210)
MEGA_KW = dict(strategy="dr", measure="tfidf", k=8, mega=True)


def test_fused_sweep_meets_case_floor():
    assert len(FUSED_MODES) * FUSED_CASES_PER_MODE >= 210


def _assert_same_result(a, b, msg=""):
    for name in ("docs", "scores", "n_found", "work", "pops", "overflowed"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=f"{name} {msg}")


@pytest.mark.parametrize("mode", FUSED_MODES)
def test_fused_beam_step_sweep_bitwise(engine, engine_corpus, mode):
    """The fused single-launch beam iteration equals the jnp pool path
    bitwise — results AND loop counters — at matched (P, Q, cap) across a
    seeded randomized sweep."""
    cases = _sweep_cases(engine_corpus, 800 + FUSED_MODES.index(mode),
                         FUSED_CASES_PER_MODE)
    for case in cases:
        plain = engine.search(case, mode=mode, **MEGA_KW)
        with backend.force_plan("gpu:interpret"):
            fused = engine.search(case, mode=mode, **MEGA_KW)
        _assert_same_result(plain, fused, f"mode={mode} case={case}")


def test_fused_empty_range_and_conjunctive_miss(engine, engine_corpus):
    """Edge rows: rare-word AND queries that intersect to nothing (empty
    ranges popped, n_found = 0) and a row mixing hit + miss words."""
    df = engine_corpus.doc_freqs()
    ids = np.arange(1, len(df))                   # id 0 is the separator
    rare = [int(w) for w in ids[df[ids] == 1][:3]]
    commons = [int(w) for w in ids[np.argsort(-df[ids])][:2]]
    assert len(rare) == 3
    case = [rare, commons + rare[:1], rare[:1] + commons]
    plain = engine.search(case, mode="and", **MEGA_KW)
    with backend.force_plan("gpu:interpret"):
        fused = engine.search(case, mode="and", **MEGA_KW)
    _assert_same_result(plain, fused, "edge rows")


def test_fused_overflow_latch_bitwise():
    """An undersized pool drops inserts and latches per-row ``overflowed``
    identically on both paths — never corrupts silently."""
    cp = corpus.make_corpus(n_docs=12, mean_doc_len=20, vocab_size=60, seed=2)
    eng = SearchEngine.build(cp, EngineConfig(block=512))
    eng._mega_cap = 2             # root fills slot 0: first split overflows
    df = cp.doc_freqs()
    pool = np.flatnonzero(df >= 4)
    q = list(map(int, pool[pool >= 1][:3]))
    plain = eng.search([q], mode="or", strategy="dr", k=5, mega=True)
    assert np.asarray(plain.overflowed).any()
    with backend.force_plan("gpu:interpret"):
        fused = eng.search([q], mode="or", strategy="dr", k=5, mega=True)
    _assert_same_result(plain, fused, "overflow latch")


# ---------------------------------------------------------------------------
# engine threading: config knob, executor-cache lowering split
# ---------------------------------------------------------------------------

def test_engine_kernel_backend_config_routes_fused(engine_corpus, engine,
                                                   query_batch):
    """``EngineConfig(kernel_backend=...)`` pins the lowering without any
    force/env — same answers, distinct compiled program."""
    pinned = SearchEngine.build(engine_corpus,
                                EngineConfig(block=512,
                                             kernel_backend="gpu:interpret"))
    a = engine.search(query_batch, mode="or", **MEGA_KW)
    b = pinned.search(query_batch, mode="or", **MEGA_KW)
    _assert_same_result(a, b, "config-pinned lowering")
    assert {k.lowering for k in pinned._executors} == {"gpu:interpret"}


def test_executor_cache_splits_on_lowering(engine, query_batch):
    """A forced plan compiles its own executor — ``ExecutorKey.lowering``
    keeps it from ever hitting a program cached under another lowering."""
    kw = dict(mode="and", **MEGA_KW)
    engine.search(query_batch, **kw)
    with backend.force_plan("gpu:interpret"):
        engine.search(query_batch, **kw)
    lows = {k.lowering for k in engine._executors if k.mega}
    assert "gpu:interpret" in lows and len(lows) >= 2


def test_invalid_kernel_backend_rejected():
    with pytest.raises(ValueError):
        EngineConfig(kernel_backend="cuda")


# ---------------------------------------------------------------------------
# active-frontier buckets: bitwise vs vmapped serial core, pad accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("conjunctive", [True, False])
@pytest.mark.parametrize("P", [1, 3, 16, 64])
def test_bucketed_batch_matches_vmapped_serial(small_index, tfidf, conjunctive,
                                               P):
    """The explicitly batched bucketed loop reproduces ``vmap(topk_dr)``
    bitwise on every result leaf — docs, scores, and the loop counters — at
    every width, including a one-word row and an all-masked row (live-width
    edge cases for the scalar bucket dispatch)."""
    idx, _ = small_index
    rng = np.random.default_rng(40 + P)
    B, Q = 5, 4
    words = jnp.asarray(rng.integers(1, idx.vocab_size, (B, Q)), jnp.int32)
    n_valid = np.array([Q, 1, 0, 2, 3])
    wmask = jnp.asarray(np.arange(Q)[None, :] < n_valid[:, None])
    idf = tfidf.idf(idx)
    kw = dict(k=5, conjunctive=conjunctive, heap_cap=64, max_pops=None,
              beam_width=P)
    got = ranked.topk_dr_batch(idx, words, wmask, idf, **kw)
    want = jax.vmap(lambda w, m: ranked.topk_dr(idx, w, m, idf, **kw))(
        words, wmask)
    for name in ("docs", "scores", "n_found", "iters", "pops", "overflowed"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=f"{name} P={P}")
    # pad waste is a property of the SCHEDULE, not the result: the batched
    # loop's bucket is the max live width across rows, so a narrow row pops
    # padded lanes the per-row adaptive bucket avoids — never fewer
    assert (np.asarray(got.padded) >= np.asarray(want.padded)).all()
    if P == 1:
        assert not np.asarray(got.padded).any()


def test_pad_waste_surfaced_in_diagnostics(engine, query_batch):
    """P=1 never pads; wider beams report per-row pad waste through
    ``SearchResults.diagnostics`` — with results invariant across widths."""
    kw = dict(mode="or", strategy="dr", measure="tfidf", k=8)
    r1 = engine.search(query_batch, beam_width=1, **kw)
    d1 = r1.diagnostics
    assert "padded" in d1 and not d1["padded"].any()
    r8 = engine.search(query_batch, beam_width=8, **kw)
    d8 = r8.diagnostics
    assert d8["padded"].shape == d8["pops"].shape
    assert (d8["padded"] >= 0).all()
    np.testing.assert_array_equal(np.asarray(r1.docs), np.asarray(r8.docs))
    np.testing.assert_array_equal(np.asarray(r1.scores), np.asarray(r8.scores))


def test_frontier_buckets_shape():
    assert ranked._frontier_buckets(1) == (1,)
    assert ranked._frontier_buckets(4) == (1, 2, 4)
    assert ranked._frontier_buckets(6) == (1, 2, 4, 6)
    assert ranked._frontier_buckets(64) == (1, 2, 4, 8, 16, 32, 64)
    idxs = [int(ranked._bucket_index(jnp.int32(n), (1, 2, 4, 6)))
            for n in (1, 2, 3, 4, 5, 6)]
    assert idxs == [0, 1, 2, 2, 3, 3]


# ---------------------------------------------------------------------------
# WTBC query-path roofline model (the numbers the counters above feed)
# ---------------------------------------------------------------------------

def test_wtbc_query_bytes_model():
    # 2 ranks x 3 levels x Q=4 x (pops+padded)=12 probes, 516 B per probe
    b = roofline.wtbc_query_bytes(pops=10, padded=2, q=4, block=512,
                                  counter_bytes=4.0)
    assert b == 2 * 3 * 4 * 12 * 516.0
    # padded lanes cost real traffic — that is the point of tracking them
    assert roofline.wtbc_query_bytes(pops=10, padded=0, q=4, block=512) < b


def test_wtbc_query_roofline_attachment():
    rl = roofline.wtbc_query_roofline(device_kind="cpu", lowering="ref",
                                      measured_us_per_query=100.0,
                                      pops=10, padded=2, q=4, block=512)
    assert rl.bytes_per_query == 2 * 3 * 4 * 12 * 516.0
    np.testing.assert_allclose(
        rl.model_us_per_query,
        rl.bytes_per_query / CHIP_PEAKS["cpu"]["hbm_bw"] * 1e6)
    np.testing.assert_allclose(rl.achieved_frac,
                               rl.model_us_per_query / 100.0)
    # the TPU lowering DMAs an aligned 8-row counter group next to each
    # tile, and its floor is the v5e HBM peak
    tpu = roofline.wtbc_query_roofline(device_kind="TPU v5 lite",
                                       lowering="tpu",
                                       measured_us_per_query=100.0,
                                       pops=10, padded=2, q=4, block=512)
    assert tpu.bytes_per_query == 2 * 3 * 4 * 12 * (512 + 8 * 1024.0)
    np.testing.assert_allclose(tpu.model_us_per_query,
                               tpu.bytes_per_query / 819e9 * 1e6)
    # a device kind without published peaks is an error, not a default
    with pytest.raises(ValueError, match="no 'hbm_bw' peak"):
        roofline.wtbc_query_roofline(device_kind="TPU v99", lowering="tpu",
                                     measured_us_per_query=100.0,
                                     pops=10, padded=2, q=4, block=512)
