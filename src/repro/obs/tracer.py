"""Dual-clock span tracer.

The co-simulation runs on two clocks at once: the **simulated
byte clock** (seconds of the bandwidth trace — deterministic, the
clock stage arrivals and session events are stamped with) and **host
wall time** (``time.perf_counter`` — what decode windows and upgrade
enqueues actually cost on this machine). A single latency number is
meaningless without saying which clock it lives on, so a
:class:`SpanRecord` carries both sides explicitly and either may be
absent: engines record wall-only spans (they never see the byte
clock), the session records sim-only spans (its work is charged by the
trace, not measured), and ``repro-telemetry`` reports always name the
clock.

Spans also feed the metrics registry (histograms
``span_<name>_wall_s`` / ``span_<name>_sim_s``) so the Prometheus and
summary exports carry the same percentiles the span list does. Like
everything in :mod:`repro.obs`, a tracer over a disabled registry
records nothing at all.

A :meth:`Tracer.span` also opens a ``jax.profiler.TraceAnnotation``
named ``repro:<name>`` with its labels as stats, so a profiler trace
shows the span on the host plane, on the thread that ran it, on the
same clock as the device's operations. Outside a profiler session the
annotation costs about a microsecond.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

from repro.obs.registry import MetricsRegistry


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    """One completed span. ``wall_s`` is host-measured duration;
    ``sim_t0``/``sim_t1`` bound the span on the simulated byte clock.
    Either clock (not both) may be absent."""

    name: str
    labels: dict
    wall_s: float | None = None
    sim_t0: float | None = None
    sim_t1: float | None = None

    @property
    def sim_s(self) -> float | None:
        if self.sim_t0 is None or self.sim_t1 is None:
            return None
        return self.sim_t1 - self.sim_t0

    def to_dict(self) -> dict:
        d = {"name": self.name, **self.labels}
        if self.wall_s is not None:
            d["wall_s"] = self.wall_s
        if self.sim_t0 is not None:
            d["sim_t0"] = self.sim_t0
        if self.sim_t1 is not None:
            d["sim_t1"] = self.sim_t1
            if self.sim_t0 is not None:
                d["sim_s"] = self.sim_s
        return d


class Tracer:
    """Span sink bound to a registry. Inert while the registry is
    disabled: ``record`` drops the span, ``span()`` skips even the
    clock reads and the profiler annotation, so tracing a disabled
    session allocates nothing."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.spans: list[SpanRecord] = []

    def record(self, name: str, *, wall_s: float | None = None,
               sim_t0: float | None = None, sim_t1: float | None = None,
               **labels) -> SpanRecord | None:
        if not self.registry.enabled:
            return None
        rec = SpanRecord(name=name, labels=labels, wall_s=wall_s,
                         sim_t0=sim_t0, sim_t1=sim_t1)
        with self.registry.lock:
            self.spans.append(rec)
        if wall_s is not None:
            self.registry.histogram(
                f"span_{name}_wall_s",
                f"host wall seconds of {name} spans").observe(
                    wall_s, **labels)
        if rec.sim_s is not None:
            self.registry.histogram(
                f"span_{name}_sim_s",
                f"simulated byte-clock seconds of {name} spans").observe(
                    rec.sim_s, **labels)
        return rec

    def span(self, name: str, *, sim_t0: float | None = None,
             sim_t1: float | None = None, **labels):
        """Measure a wall-clock span around a block, under a profiler
        annotation ``repro:<name>``; the caller may additionally stamp
        the byte-clock bounds it knows. While enabled the block gets the
        span's label dict, and labels it adds (a count known only at the
        end) go to the record and the annotation; while disabled it gets
        None."""
        if not self.registry.enabled:
            return _NULL_SPAN
        return _Span(self, name, sim_t0, sim_t1, labels)

    def of(self, name: str) -> list[SpanRecord]:
        return [s for s in self.spans if s.name == name]

    def clear(self) -> None:
        with self.registry.lock:
            self.spans.clear()


_NULL_SPAN = contextlib.nullcontext()


class _Span:
    """One open span of an enabled tracer."""

    __slots__ = ("tracer", "name", "sim_t0", "sim_t1", "opened", "labels",
                 "_ann", "_t0")

    def __init__(self, tracer: Tracer, name: str, sim_t0, sim_t1,
                 labels: dict):
        self.tracer, self.name = tracer, name
        self.sim_t0, self.sim_t1 = sim_t0, sim_t1
        self.opened, self.labels = labels, dict(labels)

    def __enter__(self) -> dict:
        from jax import profiler

        self._ann = profiler.TraceAnnotation(f"repro:{self.name}",
                                             **self.opened)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self.labels

    def __exit__(self, *exc) -> None:
        wall_s = time.perf_counter() - self._t0
        if self.labels != self.opened:
            self._ann.set_metadata(**self.labels)
        self._ann.__exit__(*exc)
        self.tracer.record(self.name, wall_s=wall_s, sim_t0=self.sim_t0,
                           sim_t1=self.sim_t1, **self.labels)
