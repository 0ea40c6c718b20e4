"""The in-process control of the JAX profiler, and the program's spans.

The second half of ``llmd_tpu.obs``. ``obs/tracing.py`` follows one request
across processes on the wall clock and exports OTLP; this module looks at
one process on the profiler's clock: ``start``/``stop`` open and close a
``jax.profiler`` session in the process that holds the chip, and ``span``
is ``jax.profiler.TraceAnnotation`` (a TraceMe), so the engine's phase
spans land on the ``/host:CPU`` plane of the same ``.xplane.pb`` that holds
the device's operations. Nothing is exported or kept by us: while no
session is open a span costs well under a microsecond and records nothing.

The cyclic collector is timed here too (``gc_watch``): a pass over the
engine's heap holds the interpreter lock on whichever thread it runs, so
it is the one host phase that no span of the step's own can bracket.

Used by ``POST /start_profile`` / ``/stop_profile`` (serve/api.py) and by
the benchmark's ``--trace 2`` (perfbench/topologies/engine.py); the span
names are listed in docs/architecture/observability.md.

``jax`` is imported inside the functions, as in ``jaxrt.py``: importing
``llmd_tpu.obs`` must stay free of it for ``epp/`` and ``sidecar/``.
"""

from __future__ import annotations

import functools
import gc
import threading
import time


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


# The collector's pauses: ``gc.callbacks`` is called at the start and the
# stop of every collection, on the thread that runs it and under the
# interpreter lock (a collection never starts inside another), so the totals
# need no lock of their own: [pause ms, collections, pause ms and collections
# of generation 2, a FULL pass over every tracked object of the process].
_gc_lock = threading.Lock()
_gc_watchers = 0  # llmd: guarded_by(_gc_lock)
_gc_totals = [0.0, 0, 0.0, 0]
_gc_open: tuple | None = None  # (span, time.monotonic()) of the collection running


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        sp = span("llmd.runner.gc", generation=info["generation"])
        sp.__enter__()
        _gc_open = (sp, time.monotonic())
    elif _gc_open is not None:  # (None: watched from inside a collection)
        sp, began = _gc_open
        _gc_open = None
        ms = (time.monotonic() - began) * 1e3
        sp.__exit__(None, None, None)
        _gc_totals[0] += ms
        _gc_totals[1] += 1
        if info["generation"] == 2:
            _gc_totals[2] += ms
            _gc_totals[3] += 1


def gc_watch() -> tuple:
    """Time every collection of this process from now on: the span
    ``llmd.runner.gc`` (attribute ``generation``) on the thread that
    collects, a child of whatever it interrupts, and the totals that
    ``gc_totals`` returns. One callback a process however many engines
    watch; each takes its watch back with ``gc_unwatch``. Returns the totals
    as they stand, the watcher's baseline."""
    global _gc_watchers
    with _gc_lock:
        _gc_watchers += 1
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
    return gc_totals()


def gc_unwatch() -> None:
    """One watcher less; the last takes the callback out of ``gc.callbacks``."""
    global _gc_watchers
    with _gc_lock:
        _gc_watchers = max(0, _gc_watchers - 1)
        if not _gc_watchers and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


def gc_totals() -> tuple:
    """(pause ms, collections, full pause ms, full collections) since the
    callback was first installed; a pause on ANY thread counts."""
    return tuple(_gc_totals)
