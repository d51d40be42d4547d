"""repro.obs -- unified tracing, metrics, and flight-recorder layer.

One import surface for instrumented code::

    from repro.obs import trace, metrics, timing, recorder

    with trace.span("tick", index=i):        # profiler annotation + "X" span
        ...
    trace.event("knob_move", value=0.2)      # instant event
    obs.count("engine.recompiles")           # counter in BOTH sinks
    m = timing.measure(fn, x, repeats=5)     # warm + block_until_ready
    rec = recorder.get_recorder()

Contracts (enforced by tests/test_obs.py and benchmarks/obs_overhead.py,
documented in docs/observability.md):

  * one clock with the device -- every span() is also a
    `jax.profiler.TraceAnnotation`, recorded whenever a profiler session
    is on; event() and counter() go to the Chrome buffer only;
  * zero-cost when disabled -- with no tracer installed, span() is one
    inactive `TraceAnnotation` and event()/counter() a single
    module-attribute read; the serving hot path shows zero extra
    compiles and >= 0.95 tick-throughput ratio;
  * never force device->host -- payloads are stored as given; lint rule
    A008 audits for traced values leaking into event payloads.
"""
from __future__ import annotations

from repro.obs import metrics, recorder, timing, trace  # noqa: F401
from repro.obs.metrics import percentile, stamp  # noqa: F401
from repro.obs.timing import Measurement, measure  # noqa: F401
from repro.obs.trace import (  # noqa: F401
    Tracer, counter, disable, enable, enabled, event, get_tracer, span,
    use,
)


def count(name: str, value: float = 1.0) -> None:
    """Increment `name` in the always-on metrics registry AND (when
    tracing) as a trace counter track -- the one-call idiom for tallies
    like cache hits and recompiles that belong in both BENCH stamps and
    Perfetto timelines."""
    metrics.registry().counter(name).inc(value)
    trace.counter(name, value)
