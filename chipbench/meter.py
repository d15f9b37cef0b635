"""Compile and persistent-cache events, from JAX's own monitoring hooks,
and the interpreter's garbage-collection pauses."""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Tuple

import jax


class CompileMeter:
    """Backend-compile seconds, and persistent-cache hits and misses,
    for the span of a ``with`` block.  A hit or a miss is one program
    that was not already in memory."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, duration: float, **_: Any) -> None:
        if event == self._COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self) -> "CompileMeter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class GcMeter:
    """The garbage collector's pauses while ``active`` is set, inside a
    ``with`` block: a diagnostic for stalls of the timed path."""

    def __init__(self) -> None:
        self.active = False
        self.pauses: List[Tuple[int, float]] = []     # (generation, s)
        self._start = 0.0

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self.active:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._start))

    def __enter__(self) -> "GcMeter":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc: Any) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> Dict[str, Any]:
        full = [s for g, s in self.pauses if g == 2]
        return {"collections": len(self.pauses), "full": len(full),
                "seconds": sum(s for _, s in self.pauses),
                "full_max_s": max(full, default=0.0)}
