"""Compile accounting from jax's own monitoring events, and the chip check."""
from __future__ import annotations

import jax


class Compiles:
    """Backend-compile seconds and count, and persistent-cache hits, since ``reset``.

    jax's listeners cannot be removed, so make one per process.
    """

    def __init__(self):
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

    def line(self) -> str:
        return f"{self.count} compiles ({self.seconds:.3f} s), {self.cache_hits} cache hits"


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; raises :class:`NoChip` without them."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (jax.devices()[0] is {devs[0].platform!r}); "
                     "the benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips and JAX sees {len(devs)}")
    return devs[:chips]


def device_record(devs, count: int) -> dict:
    """The device as JAX reports it, with the peak memory of the fullest chip used."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": count,
            "memory_peak_bytes": int(max(peaks))}
