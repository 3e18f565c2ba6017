"""Backend compile time and persistent-cache traffic, from JAX's own events."""
from __future__ import annotations


def compile_counters() -> dict:
    from jax import monitoring

    c = {"backend_compile_s": 0.0, "backend_compiles": 0, "cache_hits": 0,
         "cache_writes": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            c["backend_compile_s"] += duration
            c["backend_compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            c["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            c["cache_writes"] += 1  # JAX records this event as it writes an entry

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return c
