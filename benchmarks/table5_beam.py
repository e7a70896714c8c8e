"""Beam sweep (DESIGN.md §6): frontier-batched Algorithm 1 at P ∈ {1,4,16,64}.

For each (mode, beam_width) cell the sweep reports

* ``us_per_call`` — wall-clock per query (batched, jit-compiled),
* ``iters``      — while-loop trips summed over the query batch.  This is
  the latency-chain length of the search: each trip is one round of
  sequentially dependent rank descents, so on hardware where the batched
  rank kernel amortizes (TPU), latency tracks iters, not pops,
* ``pops``       — segments actually popped; ``pop_overhead`` = pops(P) /
  pops(1) is the price of the beam (extra expansions the one-pop order
  would have avoided),
* ``iters_ratio`` = iters(1) / iters(P) — the recorded work-metric win,
* ``padded``     — dead beam lanes popped (frontier smaller than the active
  bucket); ``pad_frac`` = padded / (pops + padded) is the wasted-descent
  share the active-frontier buckets (core/ranked.py) are meant to crush,
* a roofline attachment (``analysis/roofline.py`` WTBC query-path model):
  ``bytes_per_query`` from levels x 2 ranks x Q x (tile + counter) traffic x
  (pops + padded), and ``roofline_frac`` = memory-bound floor / measured —
  how close the cell runs to the backend's bandwidth roofline.

A ``DRmega_*`` row benches the same queries through the pool-frontier
megabatch core (``mega=True``) — the path the fused device-resident beam
step (kernels/beam_step.py) replaces trip-for-trip under a gpu lowering.

The sharded sweep runs the same queries over a simulated 4-device mesh in a
subprocess (XLA locks the device count at first init, like
``distributed_scaling``).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from benchmarks import common
from repro.analysis import roofline
from repro.engine.facade import pow2_bucket
from repro.kernels import backend as kernel_backend
from repro.text import corpus

BEAMS = (1, 4, 16, 64)

SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import time, jax
    import numpy as np
    from repro.engine import EngineConfig, SearchEngine
    from repro.text import corpus

    cp = corpus.make_corpus(n_docs=%(docs)d, mean_doc_len=120,
                            vocab_size=10000, seed=0)
    df = cp.doc_freqs()
    bands = corpus.fdoc_bands(cp.n_docs)
    qs = corpus.sample_queries(df, bands["ii"], %(nq)d, 3, seed=1)
    engine = SearchEngine.shard(cp, n_shards=4,
                                config=EngineConfig(with_drb=False))
    for P in %(beams)r:
        fn = lambda: engine.search(qs, k=10, mode="or", strategy="dr",
                                   beam_width=P)
        res = fn(); jax.block_until_ready(res.scores)      # compile
        t0 = time.time(); res = fn(); jax.block_until_ready(res.scores)
        dt = time.time() - t0
        d = res.diagnostics
        print(f"table5/sharded_cpu_DR_or_P{P},{dt/%(nq)d*1e6:.1f},"
              f"iters={int(np.sum(d['work']))};pops={int(np.sum(d['pops']))}")
""")


def run(bench: common.Bench | None = None, *, beams=BEAMS, n_queries: int = 16,
        n_words: int = 3, k: int = 10, with_sharded: bool = True,
        shard_docs: int = 800, print_rows=print) -> dict:
    b = bench or common.build()
    df = b.cp.doc_freqs()
    bands = corpus.fdoc_bands(b.cp.n_docs)
    qs = corpus.sample_queries(df, bands["ii"], n_queries, n_words, seed=5)
    results = {}

    qb = pow2_bucket(n_words)
    device_kind = jax.devices()[0].device_kind
    lowering = kernel_backend.descent_plan().tag
    block = b.engine.config.block

    def attach_roofline(rec: dict, us: float, pops: int, padded: int) -> str:
        rl = roofline.wtbc_query_roofline(
            device_kind=device_kind, lowering=lowering,
            measured_us_per_query=us,
            pops=pops / n_queries, padded=padded / n_queries,
            q=qb, block=block)
        rec.update(padded=padded,
                   pad_frac=padded / max(pops + padded, 1),
                   bytes_per_query=rl.bytes_per_query,
                   roofline_model_us=rl.model_us_per_query,
                   roofline_frac=rl.achieved_frac,
                   roofline_device_kind=device_kind,
                   roofline_lowering=lowering)
        return (f"padded={padded};bytes/q={rl.bytes_per_query:.3g};"
                f"rl_frac={rl.achieved_frac:.4f}")

    cells = [("DR", m, "dr", "tfidf") for m in ("and", "or")]
    cells += [("DRB", "and", "drb", "bm25")]
    for tag, mode, strategy, measure in cells:
        base_iters = base_pops = None
        for P in beams:
            fn = lambda: b.engine.search(qs, k=k, mode=mode,
                                         strategy=strategy, measure=measure,
                                         beam_width=P)
            dt = common.time_fn(lambda: fn().scores)
            d = fn().diagnostics
            iters = int(np.sum(d["work"]))
            pops = int(np.sum(d["pops"]))
            padded = int(np.sum(d["padded"])) if "padded" in d else 0
            if P == beams[0]:
                base_iters, base_pops = max(iters, 1), max(pops, 1)
            us = dt / n_queries * 1e6
            name = f"table5/{tag}_{mode}_P{P}"
            results[name] = {"us_per_call": us, "iters": iters, "pops": pops,
                             "iters_ratio_vs_P1": base_iters / max(iters, 1),
                             "pop_overhead_vs_P1": pops / base_pops}
            rl_str = attach_roofline(results[name], us, pops, padded)
            derived = (f"iters={iters};pops={pops};"
                       f"iters_ratio={base_iters / max(iters, 1):.2f};"
                       f"pop_overhead={pops / base_pops:.2f};{rl_str}")
            print_rows(common.csv_row(name, us, derived))

    # pool-frontier megabatch core (DESIGN.md §8) — the path the fused
    # device-resident beam step replaces trip-for-trip on a gpu lowering
    for mode in ("and", "or"):
        fn = lambda: b.engine.search(qs, k=k, mode=mode, strategy="dr",
                                     measure="tfidf", mega=True)
        dt = common.time_fn(lambda: fn().scores)
        d = fn().diagnostics
        iters = int(np.sum(d["work"]))
        pops = int(np.sum(d["pops"]))
        padded = int(np.sum(d["padded"])) if "padded" in d else 0
        us = dt / n_queries * 1e6
        name = f"table5/DRmega_{mode}"
        results[name] = {"us_per_call": us, "iters": iters, "pops": pops}
        rl_str = attach_roofline(results[name], us, pops, padded)
        print_rows(common.csv_row(name, us,
                                  f"iters={iters};pops={pops};{rl_str}"))

    if with_sharded:
        # host devices only: the child never contends for the chip
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = SHARD_SCRIPT % {"docs": shard_docs, "nq": min(n_queries, 8),
                                 "beams": tuple(beams)}
        r = subprocess.run([sys.executable, "-c", script], env=env, cwd=root,
                           capture_output=True, text=True, timeout=1800)
        for line in r.stdout.splitlines():
            if line.startswith("table5/"):
                print_rows(line)
                name, us, derived = line.split(",", 2)
                results[name] = {"us_per_call": float(us), "derived": derived}
        if r.returncode != 0:
            print_rows(f"table5/sharded_FAILED,0,{r.stderr[-200:]!r}")
    return results


if __name__ == "__main__":
    run()
