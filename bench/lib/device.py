"""The chip the benchmark runs on: finding it, its compile cache, its
memory peak, and the compilations that happen while a window runs."""
from __future__ import annotations

import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_compile_cache(path: str = CACHE_DIR) -> str:
    """Keep JAX's persistent compilation cache at ``path`` (fixed, inside
    the checkout), every program in it however small. Call before the
    first compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int) -> list:
    """The first ``chips`` TPU devices; raises ``NoChip`` naming what JAX
    found instead. Never falls back to another platform."""
    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise NoChip(f"JAX found no TPU: first device is {first.platform} "
                     f"({first.device_kind}), {len(devices)} device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)} {first.device_kind}")
    return devices[:chips]


def describe(devices) -> dict:
    """The contract's ``device`` record for ``devices``."""
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices)}


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, as the backend
    reports it (None where it reports nothing)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs handed to the backend compiler while active,
    compiled or loaded from the persistent cache."""

    def __init__(self):
        self.programs = 0
        self.active = False

    def _record(self, event, duration, **_):
        if self.active and event == _BACKEND_COMPILE:
            self.programs += 1

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._record)
        return self

    def remove(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._record)
