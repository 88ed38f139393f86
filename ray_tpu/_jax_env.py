"""The JAX seam of a worker process: which devices it may use, and where
its compiled programs are kept.

Platform selection is the plain `JAX_PLATFORMS` variable, which jax reads
at import. The raylet sets it at spawn (`core/raylet.py`
`_spawn_env_delta`): `cpu` for a worker without a TPU grant, `tpu,cpu` for
one with, so a granted worker never inherits a parent's `cpu` pin and jax
fails at backend start-up when the chip cannot be opened. No second knob
exists: nothing on the installed stack rewrites `jax_platforms` behind the
variable's back.
"""

from __future__ import annotations

import os
import time
from typing import Dict

GRANT_ENV = "RAY_TPU_GRANTED_TPU"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# One fixed path inside the checkout (git-ignored). The directory is part
# of the cache key, so it is never derived from a temp name, pid or time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compilation_cache_dir() -> str:
    """The persistent compile-cache directory this program uses, computed
    without importing jax."""
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. Where `JAX_COMPILATION_CACHE_DIR` is set jax has already
    read it and no directory is set in code; otherwise the cache goes to
    `DEFAULT_CACHE_DIR`. Every entry is kept, however small or quick: a
    fresh process on a chip pays each cold compile again otherwise."""
    import jax

    if not os.environ.get(CACHE_ENV):
        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return compilation_cache_dir()


def device_info() -> Dict[str, object]:
    """What this process computes on, as jax reports it. Initializes the
    backend; every stats/report path that names a device reads this
    (so a process that computes without a grant to claim, a CPU replica,
    has its programs timed from here on: `observability/compile.py`)."""
    from ray_tpu.observability import compile as _compile

    _compile.install()
    import jax

    devices = jax.local_devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices)}


def granted_tpu_chips() -> int:
    """Chips the raylet granted this process at spawn (0 = none)."""
    return int(os.environ.get(GRANT_ENV) or 0)


def claim_devices() -> Dict[str, object]:
    """Start-up of a process that is about to compute with jax: enable
    the compile cache, then hold the process to its grant. A worker
    spawned with a TPU grant must see exactly the granted chips on
    platform `tpu` — anything else (a CPU fallback, a neighbour's chips)
    is an error here, not a slow or wrong number later. Returns
    `device_info()` plus the cache directory.

    This is also where a process's start-up timeline learns about jax:
    the call is a lifecycle span (`jax.claim_devices`, with the seconds
    of the jax import and of the backend coming up as attributes), and
    from here on every program's trace, lowering and compile is timed
    (`observability/compile.py`)."""
    from ray_tpu.observability import compile as _compile
    from ray_tpu.observability import tracing as _tracing

    with _tracing.get_tracer().lifecycle_span(
            "jax.claim_devices", always=True) as span:
        t0 = time.monotonic()
        _compile.install()          # imports jax
        t1 = time.monotonic()
        cache_dir = enable_compilation_cache()
        info = device_info()        # starts the backend
        span.set_attr("jax_import_s", round(t1 - t0, 3))
        span.set_attr("backend_s", round(time.monotonic() - t1, 3))
        span.set_attr("platform", info["platform"])
        span.set_attr("n_devices", info["n_devices"])
        granted = granted_tpu_chips()
        if granted and (info["platform"] != "tpu"
                        or info["n_devices"] != granted):
            raise RuntimeError(
                f"worker was granted {granted} TPU chip(s) but jax reports "
                f"{info['n_devices']} local device(s) on platform "
                f"{info['platform']!r} ({info['device_kind']}); "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
                f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')!r}")
    return {**info, "compilation_cache_dir": cache_dir}
