"""The in-process control of the JAX profiler, and the program's spans.

The second half of ``llmd_tpu.obs``. ``obs/tracing.py`` follows one request
across processes on the wall clock and exports OTLP; this module looks at
one process on the profiler's clock: ``start``/``stop`` open and close a
``jax.profiler`` session in the process that holds the chip, and ``span``
is ``jax.profiler.TraceAnnotation`` (a TraceMe), so the engine's phase
spans land on the ``/host:CPU`` plane of the same ``.xplane.pb`` that holds
the device's operations. Nothing is exported or kept by us: while no
session is open a span costs well under a microsecond and records nothing.

Used by ``POST /start_profile`` / ``/stop_profile`` (serve/api.py) and by
the benchmark's ``--trace 2`` (perfbench/topologies/engine.py); the span
names are listed in docs/architecture/observability.md.

``jax`` is imported inside the functions, as in ``jaxrt.py``: importing
``llmd_tpu.obs`` must stay free of it for ``epp/`` and ``sidecar/``.
"""

from __future__ import annotations

import functools
import threading


class ProfilerBusy(RuntimeError):
    """``start`` while a session is open, or ``stop`` while none is."""


# One profiler session per process is jax's own rule (its session is
# process-global); this mirrors it so a second caller is refused here,
# by name, and ``active()`` can be asked from any thread.
_lock = threading.Lock()
_trace_dir: str | None = None  # llmd: guarded_by(_lock)


def start(trace_dir) -> None:
    """Open a profiler session writing under ``trace_dir``. Safe to call
    from another thread than the one that steps the engine.

    The Python call tracer is left off: it hooks every Python call of the
    stepping thread, which slows exactly the host phases the spans are
    there to time, and no reader of the trace uses its events."""
    import jax

    global _trace_dir
    with _lock:
        if _trace_dir is not None:
            raise ProfilerBusy(f"a profile is already being written to {_trace_dir}")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        _trace_dir = str(trace_dir)


def stop() -> str:
    """Close the session and write the trace; returns its directory. May
    take seconds (the trace is collected and serialised here)."""
    import jax

    global _trace_dir
    with _lock:
        if _trace_dir is None:
            raise ProfilerBusy("no profile is being written")
        try:
            jax.profiler.stop_trace()
        finally:
            done, _trace_dir = _trace_dir, None
    return done


def active() -> bool:
    with _lock:
        return _trace_dir is not None


def span(name: str, **attrs):
    """A context manager that writes ``name`` (with ``attrs`` as its
    stats) into the open profiler session, and nothing otherwise. What it
    yields has ``set_metadata(**attrs)`` for attributes known only inside."""
    import jax

    return jax.profiler.TraceAnnotation(name, **attrs)


def spanned(name: str):
    """Decorator: the whole call under ``span(name)``."""

    def deco(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return call

    return deco
