"""Where JAX's persistent compilation cache lives.

Every worker process compiles its own programs (a gpt-small train step,
each engine specialization), so without a shared on-disk cache each
process — and each run — compiles cold.  The rule, in one place:

  - ``JAX_COMPILATION_CACHE_DIR`` set from outside: do nothing.  JAX
    reads the variable itself, and every hop that builds a child
    environment (raylet spawn, zygote fork, container allowlist) passes
    it through.
  - unset: ``<checkout>/.jax_cache`` (git-ignored).  The path is part of
    the cache key, so it is fixed — never a temporary, pid- or
    time-derived directory.  It is exported into the environment so
    child processes inherit the same directory.
  - unset AND the process is pinned to the CPU (``JAX_PLATFORMS=cpu``:
    the test suite, lease-less workers of a chipless cluster): no cache.
    The cache exists for the chip's compile times; XLA:CPU reloads its
    cached executables with machine-feature warnings, and CPU tests
    whose timing assumes a first-request compile would see it vanish
    halfway through a run.
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Optional

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> Optional[str]:
    """Apply the rule above; returns the directory in effect, if any."""
    path = os.environ.get(_ENV)
    if path:
        return path
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (then unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@functools.cache
def _compile_totals() -> dict:
    """This process's running compile totals, kept current by JAX's
    monitoring events (listeners are registered once per process)."""
    import jax.monitoring as mon
    totals = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compile_s"] += secs

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            totals["cache_misses"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    return totals


def start_compile_clock() -> dict:
    """Mark "now" on this process's compile totals; give the mark to
    :func:`process_facts` to read what was compiled since —
    ``compile_s`` (seconds inside compile-or-load-from-cache),
    ``cache_hits`` / ``cache_misses`` (persistent cache).  A warm cache
    shows as hits and a small ``compile_s``."""
    return dict(_compile_totals())


def process_facts(since: dict) -> dict:
    """What a chip-holding process reports about itself (the train
    loop's summary, ``LLMServer.device_info``): its pid, the device as
    JAX sees it HERE, the most device memory any local device has held
    (``peak_bytes_in_use``; None where the backend keeps no such
    count), and what it compiled since ``since``
    (:func:`start_compile_clock`)."""
    import jax
    devs = jax.devices()
    now = _compile_totals()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return {"pid": os.getpid(),
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)},
            "peak_bytes_in_use": max(
                (p for p in peaks if p is not None), default=None),
            "compile_s": round(now["compile_s"] - since["compile_s"], 2),
            "cache_hits": now["cache_hits"] - since["cache_hits"],
            "cache_misses": now["cache_misses"] - since["cache_misses"]}
