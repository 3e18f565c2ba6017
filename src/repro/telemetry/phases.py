"""The phase clock: one interval per phase of a layer, reported three ways.

``with clock.phase("readback"): ...`` reads ``time.perf_counter_ns`` at
the two ends of the block and reports that one interval:

* as an integer-microsecond observation of the registry histogram
  ``<family>{<label>=<phase>}``, while telemetry is enabled
  (``REPRO_TELEMETRY``);
* as the self-trace span ``<layer>:<phase>`` (the monitor's
  ``ingest:<stage>``), where the clock was given the self-tracer and
  self-tracing is on;
* as the host event ``repro/<layer>/<phase>`` of a
  ``jax.profiler.TraceAnnotation`` around the same block, which lands on
  the profiler's host plane, on the device trace's clock, whenever a
  profiler session is active (about half a microsecond when none is).

JAX is never imported here: a process that has not loaded JAX, such as a
PS or provenance shard worker, can hold no profiler session, so there the
annotation is skipped.
"""
from __future__ import annotations

import contextlib
import sys
import time
from typing import Optional, Sequence

from . import registry
from .selftrace import SelfTracer

__all__ = ["NULL_CLOCK", "PhaseClock", "annotation"]

_NULL = contextlib.nullcontext()


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` where this process
    has loaded JAX, else a context that does nothing."""
    prof = sys.modules.get("jax.profiler")
    return _NULL if prof is None else prof.TraceAnnotation(name)


class PhaseClock:
    """The named phases of one layer, each timed by ``phase()``."""

    __slots__ = ("_hists", "_events", "_spans", "_selftrace")

    def __init__(self, layer: str, family: str, help: str, label: str,
                 phases: Sequence[str], selftrace: Optional[SelfTracer] = None):
        fam = registry.get_registry().histogram(family, help, [label])
        self._hists = {p: fam.labels(**{label: p}) for p in phases}
        self._events = {p: f"repro/{layer}/{p}" for p in phases}
        self._spans = {p: f"{layer}:{p}" for p in phases}
        self._selftrace = selftrace

    @contextlib.contextmanager
    def phase(self, name: str):
        with annotation(self._events[name]):
            t0 = time.perf_counter_ns()
            yield
            dur_us = (time.perf_counter_ns() - t0) // 1000
        self._hists[name].observe(dur_us)
        tracer = self._selftrace
        if tracer is not None and tracer.enabled:
            tracer.record(self._spans[name], t0 // 1000, dur_us)


class _NullClock:
    """A clock for callers that time nothing."""

    __slots__ = ()

    @staticmethod
    def phase(name: str):
        return _NULL


NULL_CLOCK = _NullClock()
