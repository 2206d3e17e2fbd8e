"""Process-level pieces of a run: the compile clock, the device record and
the set-up split."""
from __future__ import annotations

import time

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


class Clock:
    """Seconds JAX spends tracing, lowering and compiling, the backend
    compilations it ran, and persistent compilation-cache hits and misses,
    as its monitoring events report them."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return (self.compile_s, self.compiles, self.hits, self.misses)


class Setup:
    """The set-up split: named phases timed on the host clock from the
    process's first line, with compile seconds from the :class:`Clock`."""

    def __init__(self, t0: float) -> None:
        self.t0 = t0
        self.t_last = t0
        self.parts: dict = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self.t_last
        self.t_last = now

    def total(self) -> float:
        return self.t_last - self.t0


def require_device(chips: int):
    """The accelerator devices, or :class:`NoDevice` where JAX finds no TPU
    or fewer chips than the cell asks for. Nothing falls back to the CPU."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise NoDevice(f"JAX finds no TPU (default backend {backend!r})")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    return devs


def device_record() -> dict:
    """The device as JAX reports it, with the peak bytes in use on the
    fullest chip."""
    import jax

    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}
