"""Window readings of the program's own histograms (``repro.obs``).

The program observes a ``span.<name>`` histogram for every span it closes
(``serve.tick``, ``fleet.route``, ``fleet.mesh.put`` …) and
``serve.admit_wait_ms{loop=…}`` for every ticket a tick executes.  A
reader takes the window's mean as a Prometheus user reads
``rate(sum) / rate(count)``: from the difference of ``(count, sum)``,
summed over labels, across the window.

The window's edges are ticks.  A listener on the program's tracer
snapshots the histograms each time a ``serve.tick`` span ends, beside the
tick's start; the window's difference runs from the snapshot of the last
tick that started before the window to that of the last tick that
started inside it, so it covers the ticks that started inside the window.
Importing this module starts the recording (:data:`RECORDER`), and the
harness loads every reader, and with it this module, before the warm-up.
A program without the spans, or a window without ticks, reads None.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Sequence, Tuple

from repro.obs import REGISTRY, TRACER
from repro.obs.registry import Histogram

TICK = "serve.tick"
FAMILIES = ("span.serve.tick", "span.fleet.route", "span.fleet.mesh.put",
            "span.fleet.mesh.wait", "span.fleet.mesh.fetch",
            "serve.admit_wait_ms")

Snapshot = Dict[str, Tuple[int, float]]


class TickSnapshots:
    """``(count, sum)`` of each histogram family, summed over labels, at
    the end of every ``serve.tick`` span of ``tracer``."""

    def __init__(self, tracer, registry, families: Sequence[str] = FAMILIES):
        self.registry = registry
        self.families = tuple(families)
        self._lock = threading.Lock()
        self._base = self.read()            # before any tick seen here
        self._ticks = []                    # (tick start, Snapshot)
        tracer.add_listener(self._on_span)

    def read(self) -> Snapshot:
        out = {f: (0, 0.0) for f in self.families}
        for name, _labels, metric in self.registry.metrics():
            if name in out and isinstance(metric, Histogram):
                c, s = out[name]
                out[name] = (c + metric.count, s + metric.sum)
        return out

    def _on_span(self, span) -> None:
        if span.name == TICK:
            snap = self.read()
            with self._lock:
                self._ticks.append((span.start, snap))

    def window(self, t0: float, t1: float) -> Optional[Snapshot]:
        """``{family: (Δcount, Δsum)}`` over the ticks that started in
        ``[t0, t1)`` (``time.perf_counter`` seconds); None without one."""
        with self._lock:
            ticks = list(self._ticks)
        before, last = self._base, None
        for start, snap in ticks:
            if start < t0:
                before = snap
            elif start < t1:
                last = snap
        if last is None:
            return None
        return {f: (last[f][0] - before[f][0], last[f][1] - before[f][1])
                for f in self.families}


#: The recording every reader reads, on the program's default tracer.
RECORDER = TickSnapshots(TRACER, REGISTRY)

# the harness's window is on time.monotonic(), spans on perf_counter()
_PERF_MINUS_MONO = time.perf_counter() - time.monotonic()


def window(run, recorder: TickSnapshots = None) -> Optional[Snapshot]:
    """The differences over the run's window (``RunRecord``)."""
    t1 = run.window_end + _PERF_MINUS_MONO
    return (recorder or RECORDER).window(t1 - run.seconds, t1)


def mean(run, *families: str, per: Optional[str] = None,
         recorder: TickSnapshots = None) -> Optional[float]:
    """Summed ``Δsum`` of ``families`` over ``Δcount`` of ``per`` (the
    first family if None); None where that count did not move or any of
    the families has no observation in the window."""
    delta = window(run, recorder)
    if delta is None:
        return None
    n = delta[per or families[0]][0]
    if n <= 0 or any(delta[f][0] <= 0 for f in families):
        return None
    return sum(delta[f][1] for f in families) / n
