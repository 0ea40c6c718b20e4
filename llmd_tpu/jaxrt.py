"""Process-level JAX set-up shared by every entry point that owns a device:
which platform serves, where compiled programs are cached, and what the
process compiled. One definition, so ``python -m llmd_tpu.serve``,
``chip_smoke.py`` children and ``bench.py`` parts agree.

Nothing here runs at import time; an entry point calls what it needs from
its ``main``.
"""

from __future__ import annotations

import os
import pathlib
import re
import threading

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed, inside the checkout, listed in .gitignore: the directory is part of
# the cache key's surroundings, so a temp name, pid or timestamp never hits.
DEFAULT_COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".jax_cache"

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and this
    sets no other directory; where it is not, the cache lives at the fixed
    in-checkout path above. Tests never call this and stay without a cache.
    """
    import jax

    path = os.environ.get(COMPILE_CACHE_ENV)
    if not path:
        path = str(DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def pin_platform(requested: str | None) -> None:
    """Apply an entry point's ``--platform`` before any device is touched
    (and before ``jax.distributed`` starts). None leaves JAX's own choice,
    which ``serving_device`` then holds to a TPU."""
    if requested:
        import jax

        jax.config.update("jax_platforms", requested)


def serving_device(requested: str | None) -> dict:
    """Start the backend and return ``{"platform", "kind", "count"}`` as JAX
    reports the devices — what a server logs, exposes and is checked on.

    Without ``--platform`` the process serves from a TPU or not at all: a
    backend that fails to start raises from ``jax.devices()``, and one that
    starts on anything else is refused here — a server that finds no chip
    must not answer from the CPU as if nothing had happened. ``--platform
    cpu`` is the explicit request for the CPU (tests, the simulator
    backend).
    """
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not requested and info["platform"] != "tpu":
        raise SystemExit(
            f"llmd-tpu: JAX started on {info['platform']!r} "
            f"({info['kind']}, {info['count']} device(s)), not a TPU. "
            "Pass --platform cpu to serve from the CPU on purpose."
        )
    return info


class CompileCounters:
    """Counts what this process built: every backend compile request (a
    persistent-cache hit included — it still builds a loaded executable),
    the seconds they took, and how many were answered by the cache. Fed by
    ``jax.monitoring``; listeners cannot be removed one by one, so an entry
    point creates one instance for the life of the process."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.programs = 0  # llmd: guarded_by(_lock)
        self.seconds = 0.0  # llmd: guarded_by(_lock)
        self.cache_hits = 0  # llmd: guarded_by(_lock)

    def install(self) -> "CompileCounters":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == _BACKEND_COMPILE_EVENT:
            with self._lock:
                self.programs += 1
                self.seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "programs": self.programs,
                "seconds": round(self.seconds, 3),
                "cache_hits": self.cache_hits,
            }


def peak_bytes_in_use() -> int | None:
    """Largest ``peak_bytes_in_use`` over the local devices, or None where
    the backend keeps no memory statistics (the CPU)."""
    import jax

    peaks = [
        stats["peak_bytes_in_use"]
        for stats in (d.memory_stats() for d in jax.local_devices())
        if stats and "peak_bytes_in_use" in stats
    ]
    return max(peaks) if peaks else None


def held_device_files() -> list[str]:
    """The accelerator device files this process holds open (a v5e chip is
    ``/dev/vfio/<n>``; ``/dev/vfio/vfio`` is the container every user of
    VFIO opens) — which chips it owns as the kernel sees it, whatever JAX
    numbers them. Two one-chip replicas on one host must not share one."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:  # closed between the listing and the read
            continue
        if re.fullmatch(r"/dev/(accel|vfio/)\d+", target):
            held.add(target)
    return sorted(held)
