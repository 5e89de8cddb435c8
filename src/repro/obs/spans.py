"""Host spans of the program, on the profiler's clock.

``span(name)`` marks a stretch of host work as ``repro:<name>`` with a
``jax.profiler.TraceAnnotation``: while a profiler trace records, the span
lands on the host plane of the same ``.xplane.pb`` as the device's
operations, so an idle gap on the device can be put down to the host work
that made it. With a registry the span's seconds also go, traced or not,
into the histogram ``service_tick_phase_seconds{phase}`` (``/metrics``).
Without a profiler recording, a span costs a few microseconds.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax

from repro.obs.metrics import SECONDS_BUCKETS

__all__ = ["span"]

SPAN_PREFIX = "repro:"
PHASE_HISTOGRAM = "service_tick_phase_seconds"


@contextmanager
def span(name: str, registry=None):
    """Time the block as the host span ``repro:<name>``; with a
    ``MetricsRegistry``, observe its seconds under ``phase=<name>``."""
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
    finally:
        if registry is not None:
            registry.histogram(
                PHASE_HISTOGRAM, "host seconds of each phase of a service "
                "tick", ("phase",), buckets=SECONDS_BUCKETS).labels(
                    phase=name).observe(time.perf_counter() - start)
