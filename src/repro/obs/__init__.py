"""Unified observability: metrics registry, span tracing, exporters.

Dependency-free (stdlib only) and imported BY the engine/executor/
serving layers — never the reverse — so it sits at the bottom of the
dependency graph next to :mod:`repro.api.plan`.

Quick tour::

    from repro import obs

    obs.counter("deepmap_executor_morsels_total").inc(kind="scan")
    with obs.span("collect", morsel=0) as sp:
        ...                                  # timed work
    print(sp.seconds)                        # measured even when disabled
    print(obs.to_prometheus())               # /metrics scrape body
    obs.write_chrome_trace("trace.json")     # open in Perfetto

``obs.set_enabled(False)`` flips both the registry and tracer to
no-ops in one call — used by the benchmarks to measure the always-on
overhead (<3% QPS budget, recorded in BENCH_lookup.json).

``with``-spans are mirrored into the JAX profiler once a module that
imports JAX installs the annotation factory (:func:`set_profiler_hook`);
:data:`PROFILER_SPANS` lists the ones the served path opens.
"""

from repro.obs.export import (
    to_chrome_trace,
    to_json_snapshot,
    to_prometheus,
    write_chrome_trace,
    write_json_snapshot,
    write_prometheus,
)
from repro.obs.metrics import (
    LATENCY_BUCKETS,
    SIZE_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    registry,
    set_registry,
)
from repro.obs.tracing import (
    PROFILER_SPANS,
    Span,
    Tracer,
    set_profiler_hook,
    set_tracer,
    tracer,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS",
    "MetricsRegistry",
    "PROFILER_SPANS",
    "SIZE_BUCKETS",
    "Span",
    "Tracer",
    "counter",
    "gauge",
    "histogram",
    "registry",
    "set_enabled",
    "set_profiler_hook",
    "set_registry",
    "set_tracer",
    "snapshot",
    "span",
    "to_chrome_trace",
    "to_json_snapshot",
    "to_prometheus",
    "tracer",
    "write_chrome_trace",
    "write_json_snapshot",
    "write_prometheus",
]


def counter(name: str, help: str = "") -> Counter:
    """``registry().counter(...)`` on the current default registry."""
    return registry().counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    """``registry().gauge(...)`` on the current default registry."""
    return registry().gauge(name, help)


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    """``registry().histogram(...)`` on the current default registry."""
    return registry().histogram(name, help, buckets=buckets)


def span(name: str, track: str = "host", **args):
    """``tracer().span(...)`` on the current default tracer: a context
    manager whose ``seconds`` is the block's duration."""
    return tracer().span(name, track=track, **args)


def snapshot() -> dict:
    """JSON-able dump of the current default registry."""
    return registry().snapshot()


def set_enabled(enabled: bool) -> None:
    """Flip BOTH the default registry and default tracer on/off —
    the one-flag kill-switch for overhead measurement."""
    registry().enabled = enabled
    tracer().enabled = enabled
