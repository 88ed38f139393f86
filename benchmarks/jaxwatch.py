"""Counts what jax compiles and what its persistent cache serves, in the
process that holds the chip (as `chip_smoke.py` does): compilations inside
the measured window must be 0."""

from __future__ import annotations

from typing import Dict


def watch() -> Dict[str, int]:
    """Registers the listeners; the returned dict counts from now on."""
    import jax

    seen = {"hits": 0, "misses": 0, "compiles": 0}

    def on_event(event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            seen["hits"] += 1
        elif event.endswith("compilation_cache/cache_misses"):
            seen["misses"] += 1

    def on_duration(event, duration, **_):
        if event.endswith("backend_compile_duration"):
            seen["compiles"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen
