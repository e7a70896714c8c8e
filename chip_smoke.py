"""Chip smoke: drive the served WTBC search path once on a TPU and check it.

    python chip_smoke.py                # one chip: build -> snapshot -> serve
    python chip_smoke.py --four-chips   # only the 4-way sharded engine

One process, one chip (or the four chips of one host).  The collection is the
deployment of ``configs/wtbc_paper.py`` (four 4,194,304-token shards, 6,750
documents each) made from ``--seed`` with ``text/corpus.make_corpus``:
27,000 documents, mean length ~621 tokens, vocabulary 200,000, counter
block 32768.  The one-chip run boots the engine the way a server does
(``SearchEngine.build`` -> ``snapshot.save`` -> ``snapshot.load``), warms a
``SearchServer`` and sends a few dozen requests: DR and/or tf-idf at beam 1
and 16, the megabatch DR/or core, DRB/or BM25 and phrase.  Every answer is
checked against the NumPy oracle of ``tests/oracle.py``; the DR and/or
answers are also checked bitwise against the same engine on the jnp ``ref``
lowering.  ``--four-chips`` shards the same collection 4 ways (build and
snapshot round trip), prints the bytes each device holds, and checks the
sharded answers against the oracle and a one-chip engine on device 0.

Any failure exits non-zero; so does a run that finds no TPU.  The last line
of standard output is the JSON verdict.  The times printed are phase wall
times of a smoke run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import pathlib
import sys
import tempfile
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent
RTOL, ATOL = 2e-5, 1e-4          # tests/test_oracle_diff.py tolerances
N_DOCS, MEDIAN_DOC_LEN, VOCAB, BLOCK = 27_000, 519, 200_000, 32768
PER_PROFILE, K, MAX_BATCH = 6, 10, 4

# (name, QueryProfile fields) — the traffic mix; df_cap of the DRB/or
# profile is pinned from the query population at run time
PROFILES = (
    ("dr_and_p1", dict(mode="and", strategy="dr", measure="tfidf",
                       beam_width=1)),
    ("dr_or_p1", dict(mode="or", strategy="dr", measure="tfidf",
                      beam_width=1)),
    ("dr_and_p16", dict(mode="and", strategy="dr", measure="tfidf",
                        beam_width=16)),
    ("dr_or_p16", dict(mode="or", strategy="dr", measure="tfidf",
                       beam_width=16)),
    ("dr_or_mega", dict(mode="or", strategy="dr", measure="tfidf",
                        mega=True)),
    ("drb_or_bm25", dict(mode="or", strategy="drb", measure="bm25")),
    ("phrase", dict(mode="phrase", measure="tfidf")),
)
# profiles the sharded backend serves (phrase is single-host only; mega
# normalises off there, so it would repeat dr_or_p1)
SHARDED_PROFILES = ("dr_and_p1", "dr_or_p1", "dr_and_p16", "dr_or_p16",
                    "drb_or_bm25")


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Phases:
    """Wall time per phase, printed as each phase ends."""

    def __init__(self):
        self.t = time.perf_counter()

    def done(self, name: str, **info) -> None:
        now = time.perf_counter()
        extra = "".join(f" {k}={v}" for k, v in info.items())
        print(f"phase {name}: {now - self.t:.3f} s{extra}", flush=True)
        self.t = now


# ---------------------------------------------------------------------------
# collection, traffic, oracle
# ---------------------------------------------------------------------------

def make_traffic(cp, df, seed: int) -> dict[str, list[list[int]]]:
    """Per-profile queries: two words each from the paper's df bands i-iv
    (rotating), phrase queries lifted from documents as 2-grams."""
    from repro.text import corpus
    bands = list(corpus.fdoc_bands(cp.n_docs).values())
    out = {}
    for p, (name, prof) in enumerate(PROFILES):
        if prof["mode"] == "phrase":
            qs = corpus.sample_ngram_queries(
                cp.doc_tokens, PER_PROFILE, 2, seed=seed + p, df=df,
                df_cap=bands[2][1])
        else:
            qs = [corpus.sample_queries(df, bands[i % len(bands)], 1, 2,
                                        seed=seed + 100 * p + i)[0]
                  for i in range(PER_PROFILE)]
        out[name] = [[int(w) for w in q] for q in qs]
    return out


def oracle_answers(cp, traffic, names) -> dict:
    """Full oracle rankings, {(profile, i): {doc: score}} — host NumPy."""
    import oracle
    prof = dict(PROFILES)
    out = {}
    for name in names:
        p = prof[name]
        for i, q in enumerate(traffic[name]):
            exp = oracle.search_oracle(
                cp.doc_tokens, q, mode=p["mode"], measure=p["measure"],
                strategy=p.get("strategy", "dr"), vocab_size=cp.vocab_size)
            out[name, i] = {d: e["score"] for d, e in exp.items()}
    return out


class OracleThread(threading.Thread):
    """The oracle rescans the raw tokens on the host; it runs while the
    chip builds, compiles and serves, and is joined at the check."""

    def __init__(self, cp, traffic, names):
        super().__init__(daemon=True, name="oracle")
        self.args, self.result, self.error = (cp, traffic, names), None, None

    def run(self):
        try:
            self.result = oracle_answers(*self.args)
        except Exception as e:               # re-raised by answers()
            self.error = e

    def answers(self) -> dict:
        self.join()
        if self.error is not None:
            raise self.error
        return self.result


def close(a, b) -> bool:
    return abs(a - b) <= ATOL + RTOL * abs(b)


def check_against_oracle(name, i, row, exp) -> bool:
    """Returned docs carry their oracle scores; the k-th returned score is
    >= every unreturned eligible doc's oracle score (minus the tolerance),
    which holds whatever order ties were broken in.  Returns non-empty."""
    hits = row.hits()
    where = f"{name} query {i}"
    check(not row.overflowed, f"{where}: search heap overflowed")
    check(len(hits) == min(K, len(exp)),
          f"{where}: {len(hits)} hits, oracle has {len(exp)} eligible docs")
    for d, s in hits:
        check(d in exp, f"{where}: doc {d} is not eligible in the oracle")
        check(close(s, exp[d]), f"{where}: doc {d} score {s} != oracle "
                                f"{exp[d]}")
    if hits:
        kth = min(s for _, s in hits)
        got = {d for d, _ in hits}
        worst = max((s for d, s in exp.items() if d not in got),
                    default=float("-inf"))
        check(kth >= worst - (ATOL + RTOL * abs(worst)),
              f"{where}: unreturned doc scores {worst} > k-th {kth}")
    return bool(hits)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def profiles_for(engine, traffic, names):
    from repro.serve.batcher import QueryProfile
    prof = dict(PROFILES)
    out = {}
    for name in names:
        fields = dict(prof[name], k=K)
        if fields.get("strategy") == "drb" and fields["mode"] == "or":
            fields["df_cap"] = engine.suggested_df_cap(traffic[name])
        out[name] = QueryProfile(**fields)
    return out


def serve(engine, traffic, profiles, phases) -> dict:
    """Warm a SearchServer for every profile, then send the traffic from a
    few client threads; returns {(profile, i): RowResult}."""
    from repro.serve.server import SearchServer
    server = SearchServer(engine, max_batch=MAX_BATCH, queue_depth=256)
    n_exec = sum(server.warmup(traffic[n], profiles[n]) for n in profiles)
    traces0 = server.stats["traces"]
    phases.done("compile", executors=n_exec)
    jobs = [(n, i, q) for n in profiles for i, q in enumerate(traffic[n])]
    rows = {}
    with server, concurrent.futures.ThreadPoolExecutor(MAX_BATCH) as pool:
        for name in profiles:        # one profile at a time, timed apart
            t0 = time.perf_counter()
            futs = {pool.submit(server.search, q, profiles[name], 600.0):
                    (name, i) for i, q in enumerate(traffic[name])}
            for f in concurrent.futures.as_completed(futs):
                rows[futs[f]] = f.result()
            got = [rows[name, i] for i in range(len(traffic[name]))]
            print(f"  {name}: {time.perf_counter() - t0:.3f} s for "
                  f"{len(got)} requests; pops {[r.pops for r in got]} "
                  f"trips {[r.work for r in got]}", flush=True)
    st = server.stats
    phases.done("traffic", requests=len(jobs), served=st["served"],
                errors=st["errors"], shed=st["shed"],
                retraces=st["traces"] - traces0,
                batches=st["batch_hist"])
    check(st["errors"] == 0, f"{st['errors']} requests errored")
    check(st["shed"] == 0, f"{st['shed']} requests shed")
    check(st["served"] + st["cache"].get("hits", 0) >= len(jobs)
          and len(rows) == len(jobs), "not every request was answered")
    check(st["traces"] == traces0,
          f"{st['traces'] - traces0} retraces after warmup")
    return rows


def kernel_in_executors(engine, traffic, profiles, names) -> None:
    """The lowered program of every listed profile calls the Pallas TPU
    kernel (``tpu_custom_call``)."""
    for name in names:
        text = engine.lower([traffic[name][0]],
                            **profiles[name].search_kwargs()).as_text()
        check("tpu_custom_call" in text,
              f"{name}: lowered executor has no tpu_custom_call")
    print(f"tpu_custom_call in the lowered executors of {', '.join(names)}",
          flush=True)


def check_rows(rows, expected, names) -> None:
    """Every answer against the oracle; all mismatches are reported."""
    n_hit = {n: 0 for n in names}
    bad = []
    for (name, i), row in sorted(rows.items()):
        try:
            n_hit[name] += check_against_oracle(name, i, row,
                                                expected[name, i])
        except SmokeFailure as e:
            bad.append(str(e))
    check(not bad, f"{len(bad)} of {len(rows)} answers disagree with the "
                   f"oracle: " + "; ".join(bad[:8]))
    print(f"oracle: {len(rows)} answers within rtol={RTOL} atol={ATOL}; "
          f"non-empty per profile {n_hit}", flush=True)


def compare_engines(rows, other, traffic, profiles, names, label,
                    bitwise=True) -> None:
    """``other`` answers the same queries as the served rows: same docs,
    and scores bitwise equal (or within the oracle tolerance)."""
    import numpy as np
    n = n_bitwise = 0
    for name in names:
        res = other.search(traffic[name], **profiles[name].search_kwargs())
        docs, scores = np.asarray(res.docs), np.asarray(res.scores)
        for i in range(len(traffic[name])):
            row = rows[name, i]
            same = (np.array_equal(row.docs, docs[i])
                    and np.array_equal(row.scores, scores[i]))
            n += 1
            n_bitwise += same
            if bitwise:
                check(same, f"{name} query {i}: {label} answer differs")
            else:
                check(np.array_equal(row.docs, docs[i])
                      and np.allclose(row.scores, scores[i], rtol=RTOL,
                                      atol=ATOL),
                      f"{name} query {i}: {label} answer differs")
    print(f"{label}: {n} answers compared, {n_bitwise} bitwise equal",
          flush=True)


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------

def check_plan() -> None:
    from repro.kernels import backend
    plan = backend.descent_plan()
    print(f"plan: {plan.tag} {plan}", flush=True)
    check(plan == backend.KernelPlan("tpu", False),
          f"resolved plan {plan}, want KernelPlan('tpu', False)")


def dr_profiles(profiles) -> list[str]:
    """The profiles whose executors run the descent kernel."""
    return [n for n, p in profiles.items()
            if p.strategy == "dr" and p.mode in ("and", "or")]


def one_chip(cp, traffic, oracle_thread, phases, tmp) -> None:
    import jax
    from repro.engine import EngineConfig, SearchEngine
    from repro.serve import snapshot

    check_plan()
    config = EngineConfig(block=BLOCK)
    built = SearchEngine.build(cp, config)
    jax.block_until_ready(built.idx)
    phases.done("build")
    snapshot.save(built, tmp)
    phases.done("snapshot_save")
    del built
    engine = snapshot.load(tmp)
    jax.block_until_ready((engine.idx, engine.aux))
    phases.done("snapshot_load")
    rep = engine.space_report()
    print(f"index bytes: {rep['total']} ({rep['total'] / cp.n_tokens:.4f} "
          f"bytes/token); space report {rep}", flush=True)
    mem = jax.devices()[0].memory_stats() or {}
    print(f"device bytes_in_use after load: {mem.get('bytes_in_use')}",
          flush=True)

    names = [n for n, _ in PROFILES]
    profiles = profiles_for(engine, traffic, names)
    rows = serve(engine, traffic, profiles, phases)
    dr = dr_profiles(profiles)
    kernel_in_executors(engine, traffic, profiles, dr)
    ref = SearchEngine._restore(
        config=dataclasses.replace(engine.config, kernel_backend="ref"),
        model=engine.model, n_docs=engine.n_docs, backend="single",
        idx=engine.idx, aux=engine.aux)
    compare_engines(rows, ref, traffic, profiles, dr, "tpu vs ref plan")
    phases.done("ref_compare")
    check_rows(rows, oracle_thread.answers(), names)
    phases.done("oracle_check")


def four_chips(cp, traffic, oracle_thread, phases, tmp) -> None:
    import jax
    from repro.engine import EngineConfig, SearchEngine
    from repro.serve import snapshot

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
    check_plan()
    config = EngineConfig(block=BLOCK)
    built = SearchEngine.shard(cp, 4, config)
    jax.block_until_ready((built.idx, built.aux))
    phases.done("shard_build")
    snapshot.save(built, tmp)
    phases.done("snapshot_save")
    del built
    engine = snapshot.load(tmp)
    jax.block_until_ready((engine.idx, engine.aux))
    phases.done("snapshot_load")

    per_device: dict[int, int] = {}
    for leaf in jax.tree.leaves((engine.idx, engine.aux)):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (per_device.get(shard.device.id, 0)
                                           + shard.data.nbytes)
            check(shard.data.shape[:1] == (1,),
                  f"leaf of shape {leaf.shape} not split 4 ways")
    print(f"bytes per device (sharded index + DRB): {per_device}", flush=True)
    check(len(per_device) == 4, f"index spans {len(per_device)} devices")

    single = SearchEngine.build(cp, config)      # on devices[0]
    jax.block_until_ready(single.idx)
    check({d for leaf in jax.tree.leaves(single.idx) for d in leaf.devices()}
          == {devices[0]}, "one-chip engine is not on devices[0]")
    phases.done("single_build")

    names = list(SHARDED_PROFILES)
    profiles = profiles_for(engine, traffic, names)
    rows = serve(engine, traffic, profiles, phases)
    kernel_in_executors(engine, traffic, profiles, dr_profiles(profiles))
    # tf-idf sums the same per-word terms in the same order on both; BM25's
    # average document length is summed differently by the two backends
    tfidf = [n for n in names if profiles[n].measure == "tfidf"]
    # the one-chip engine pins its own DRB/or gather width
    single_profiles = profiles_for(single, traffic, names)
    compare_engines(rows, single, traffic, single_profiles, tfidf,
                    "sharded vs one-chip")
    compare_engines(rows, single, traffic, single_profiles,
                    [n for n in names if n not in tfidf],
                    "sharded vs one-chip (bm25, within tolerance)",
                    bitwise=False)
    phases.done("single_compare")
    check_rows(rows, oracle_thread.answers(), names)
    phases.done("oracle_check")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way sharded engine on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    try:
        import oracle  # noqa: F401  (tests/oracle.py)
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: run me from the root of a checkout of the repo "
              f"({e})", file=sys.stderr)
        return 2
    if not pathlib.Path(compile_cache.__file__).resolve().is_relative_to(ROOT):
        print(f"chip_smoke: repro imported from {compile_cache.__file__}, "
              f"not from this checkout", file=sys.stderr)
        return 2
    cache = compile_cache.place_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}, {len(devices)} devices)", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)

    from repro.text import corpus
    phases = Phases()
    cp = corpus.make_corpus(n_docs=N_DOCS, mean_doc_len=MEDIAN_DOC_LEN,
                            vocab_size=VOCAB, seed=args.seed)
    df = cp.doc_freqs()
    traffic = make_traffic(cp, df, args.seed)
    phases.done("corpus", docs=cp.n_docs, tokens=cp.n_tokens,
                mean_doc_len=f"{cp.n_tokens / cp.n_docs - 1:.1f}",
                vocab=cp.vocab_size)
    names = SHARDED_PROFILES if args.four_chips else [n for n, _ in PROFILES]
    oracle_thread = OracleThread(cp, traffic, names)
    oracle_thread.start()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_snap_") as tmp:
            (four_chips if args.four_chips else one_chip)(
                cp, traffic, oracle_thread, phases, tmp)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
