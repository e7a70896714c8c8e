"""repro.obs — unified metrics, per-request span tracing, and exporters.

The observability layer the serving stack, engine facade and kernel
dispatcher record into (DESIGN.md §10):

    metrics    Counter / Gauge / log2-sub-bucketed Histogram + Registry
               (disabled-by-default process registry; zero-cost when off)
    tracing    spans of the serving path in one bounded in-memory ring
               (disabled-by-default process recorder ``SPANS``)
    export     Prometheus text format, JSONL snapshots, HTTP endpoint, dump()

Quick use::

    import repro.obs as obs
    obs.enable()                       # flip the process-default registry on
    obs.enable_spans()                 # and the span recorder (stage
                                       # histograms are fed from the spans)
    ...serve traffic...
    print(obs.render_prometheus())     # or obs.dump() for plain data
    log = obs.SPANS.drain()            # the spans, and how many dropped

Pure Python, no jax dependency — importable from anywhere in the stack
without cycles or device side effects.
"""
from repro.obs.export import (MetricsServer, dump, render_prometheus,
                              snapshot_line, write_jsonl)
from repro.obs.metrics import (SUBBUCKETS, Counter, Gauge, Histogram,
                               Registry, default_registry, enable, resolve,
                               use)
from repro.obs.tracing import SPANS, Span, SpanLog, SpanRecorder
from repro.obs.tracing import enable as enable_spans

__all__ = [
    "SPANS", "SUBBUCKETS", "Counter", "Gauge", "Histogram", "MetricsServer",
    "Registry", "Span", "SpanLog", "SpanRecorder", "default_registry", "dump",
    "enable", "enable_spans", "render_prometheus", "resolve",
    "snapshot_line", "use", "write_jsonl",
]
