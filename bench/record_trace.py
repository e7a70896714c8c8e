"""Record a small device trace of the served search path, for the tests of the
trace reduction (``bench/tests/test_trace.py``).

    python bench/record_trace.py --out bench/tests/data/trace_small

Builds a small engine on the accelerator (600 documents, counter block 4096),
warms DR/OR tf-idf at batch 2, and traces one search with a clock
annotation (``trace.CLOCK_ANNOTATION``) taken on the host just after the
trace starts.  Writes the ``.xplane.pb`` under ``--out`` and prints what the
reduction reads from it: the device planes, their lines, the events by name,
and the clock annotation.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import pathlib
import shutil
import sys
import tempfile

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent),
                str(pathlib.Path(__file__).resolve().parents[1] / "src")]

from lib import trace  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="record on the CPU (for trying the script only)")
    args = ap.parse_args()

    import jax
    import numpy as np
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"record_trace: no TPU (found {dev.platform})", file=sys.stderr)
        return 1
    from repro.engine import EngineConfig, SearchEngine
    from repro.text import corpus

    cp = corpus.make_corpus(n_docs=600, mean_doc_len=80, vocab_size=3000,
                            seed=5)
    engine = SearchEngine.build(cp, EngineConfig(block=4096))
    df = cp.doc_freqs()
    qs = [[int(w) for w in q] for q in
          corpus.sample_queries(df, (5, 60), 2, 2, seed=1)]
    kw = dict(mode="or", strategy="dr", measure="tfidf", k=10)
    np.asarray(engine.search(qs, **kw).docs)           # compile outside
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    rec = trace.Recorder(tmp)
    rec.start()
    np.asarray(engine.search(qs, **kw).docs)
    rec.stop()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    dst = out / "trace.xplane.pb"
    shutil.copy(trace.find_xplane(tmp), dst)
    shutil.rmtree(tmp)
    print(f"device: {dev.platform} {dev.device_kind}; wrote {dst} "
          f"({dst.stat().st_size} bytes); clock offset {rec.offset_ns} ns")
    print(trace.describe(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
