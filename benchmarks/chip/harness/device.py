"""The device stamp, the accelerator check, the compile cache and the
count of compilations inside the measured window."""
from __future__ import annotations

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int) -> list:
    """The first `chips` TPU devices; raises when JAX finds no TPU or
    fewer chips than the cell asks for."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds "
                            f"{len(devs)}")
    return devs[:chips]


def stamp(devices: list) -> dict:
    d = devices[0]
    return dict(platform=d.platform, kind=d.device_kind,
                count=len(jax.devices()))


def memory_peak_bytes(devices: list) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def use_compile_cache() -> str:
    """JAX's persistent compile cache at the program's fixed directory
    inside the checkout (`.jax_cache/`, whatever the environment names),
    with every program cached however quickly it compiled, so that only a
    cell's first run in a checkout compiles."""
    from repro.compile_cache import REPO_CACHE_DIR

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(REPO_CACHE_DIR)


class CompileCounter:
    """Counts XLA backend compiles through the jax.monitoring event stream
    (the listener of the repository's test suite)."""

    def __init__(self):
        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **kw):
        if self.active and name == _COMPILE_EVENT:
            self.count += 1
