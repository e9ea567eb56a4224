"""Counts what JAX compiles in this process, from its monitoring events.

The benchmark's own copy of the listeners in
``ray_tpu/_private/compile_cache.py`` (listed in PERF.md): a later PR may
change the program's, and "nothing compiled inside the window" must keep
meaning the same thing.
"""

import functools
import logging

_DURATIONS = []        # seconds of each compile-or-load, in order
_NAMES = []            # "jit(name) shapes" of each program JAX lowers


class _Names(logging.Filter):
    """Reads the program's name off JAX's own debug line and lets no
    debug line through (warnings pass as before)."""

    def filter(self, record: logging.LogRecord) -> bool:
        if record.levelno > logging.DEBUG:
            return True
        msg = record.getMessage()
        if msg.startswith("Compiling ") and len(_NAMES) < 4096:
            _NAMES.append(msg[10:400])
        return False


@functools.cache
def _totals() -> dict:
    import jax.monitoring as mon
    totals = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
              "backend_compiles": 0}
    _DURATIONS[:] = []

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compile_s"] += secs
            totals["backend_compiles"] += 1
            if len(_DURATIONS) < 4096:
                _DURATIONS.append(secs)

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            totals["cache_misses"] += 1

    lowering = logging.getLogger("jax._src.interpreters.pxla")
    lowering.setLevel(logging.DEBUG)
    lowering.addFilter(_Names())
    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    return totals


def snapshot() -> dict:
    return dict(_totals(), named=len(_NAMES))


def longest_since(mark: dict) -> float:
    """The longest single compile-or-load after ``mark`` (seconds)."""
    return max(_DURATIONS[int(mark["backend_compiles"]):], default=0.0)


def names_since(mark: dict) -> list:
    return _NAMES[int(mark.get("named", 0)):][:40]
