"""Device profiling — ``jax.profiler`` trace capture and a compile counter.

The registry/tracer pair measures *host-side* wall time; what the device
actually did lives in the XLA trace.  Every :class:`~repro.obs.SpanTracer`
span is also a ``jax.profiler.TraceAnnotation`` of its name, so a
captured trace lines the host spans up with the device's operations on
one clock.  A TPU trace carries no op metadata, so ``jax.named_scope``
inside a jitted program does not show there; the device side is named
only by its XLA instructions and by the Pallas kernels' own names.

Capture is strictly opt-in (profiling is not free):

    with engine.capture_device_trace("/tmp/trace"):
        engine.run(queries)

then open the directory with TensorBoard's profile plugin or
``xprof``.  See docs/OBSERVABILITY.md for the full how-to.

:func:`install_compile_counter` feeds the ``jax.lowerings`` counter: one
increment per program JAX lowers to MLIR, which is what a new batch shape
(or a cold jit) costs before XLA compiles or loads it from a cache.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

from repro.obs.registry import REGISTRY, Counter

__all__ = ["device_trace", "install_compile_counter", "lowerings"]

_LOWER_EVENT = "jaxpr_to_mlir_module"
_install_lock = threading.Lock()
_installed = False


@contextmanager
def device_trace(log_dir):
    """Capture a ``jax.profiler`` trace of the enclosed block into
    ``log_dir`` (created if missing).  Reentrant use raises — jax allows
    one active trace per process."""
    import jax
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def lowerings() -> Counter:
    """The ``jax.lowerings`` counter: programs lowered to MLIR in this
    process (jax's ``jaxpr_to_mlir_module`` event) since
    :func:`install_compile_counter` ran."""
    return REGISTRY.counter("jax.lowerings")


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if _LOWER_EVENT in event:
        lowerings().inc()


def install_compile_counter() -> Counter:
    """Register, once per process, the ``jax.monitoring`` listener that
    counts lowerings into ``jax.lowerings``; returns the counter."""
    global _installed
    with _install_lock:
        if not _installed:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _installed = True
    return lowerings()
