"""Distributed-index scaling (paper §5: "a cluster that implements a large
in-memory distributed index"): same corpus, 1 vs 8 document shards, batched
query latency.  Runs in a subprocess on 8 simulated host devices
(``JAX_PLATFORMS=cpu``, rows labelled ``cpu``), so it never takes the chip."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import time, jax
    from repro.engine import EngineConfig, SearchEngine
    from repro.text import corpus

    cp = corpus.make_corpus(n_docs=2000, mean_doc_len=150, vocab_size=20000, seed=0)
    df = cp.doc_freqs()
    bands = corpus.fdoc_bands(cp.n_docs)
    qs = corpus.sample_queries(df, bands["ii"], 16, 3, seed=1)

    for n_shards in (1, 8):
        engine = SearchEngine.shard(cp, n_shards=n_shards,
                                    config=EngineConfig(with_drb=False))
        fn = lambda: engine.search(qs, k=10, mode="or", strategy="dr").scores
        jax.block_until_ready(fn())     # compile
        t0 = time.time(); jax.block_until_ready(fn()); dt = time.time() - t0
        print(f"distributed/cpu_dr-or_shards{n_shards},"
              f"{dt/16*1e6:.1f},{dt/16*1e3:.3f}ms/query")
""")


def run(print_rows=print):
    # host devices only: the child never contends for the chip
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=root,
                       capture_output=True, text=True, timeout=1800)
    for line in r.stdout.splitlines():
        if line.startswith("distributed/"):
            print_rows(line)
    if r.returncode != 0:
        print_rows(f"distributed/FAILED,0,{r.stderr[-200:]!r}")


if __name__ == "__main__":
    run()
