"""``jax.monitoring`` listeners: live compile counters + steady-state
recompile flagging.

XLA recompiles are the silent throughput killer of a JAX service: one
stray shape change turns a 2 ms decode step into a 30 s stall, and
nothing in the program output says so.  JAX already emits monitoring
events for every backend compile (``/jax/core/compile/
backend_compile_duration`` — the same hooks TensorBoard's profiler
consumes); this module folds them into the metrics registry:

* ``fdtpu_jax_compiles_total`` / ``fdtpu_jax_compile_seconds_total`` —
  every backend compile, count and wall seconds; each is also a
  ``compile`` span ``[now - duration, now]`` in the process tracer
  (:mod:`.spans`), carrying the loader item during which it fell;
* ``fdtpu_jax_trace_seconds_total`` — jaxpr tracing time (host-side
  program construction, distinct from XLA compile time);
* ``fdtpu_jax_steady_recompiles_total`` — compiles that happened AFTER
  the caller declared steady state.  The serve engine's "ONE decode
  compile" invariant (tests assert it offline) becomes a live metric:
  scrape nonzero here in production and something is recompiling.
* ``fdtpu_jax_cache_hits_total`` / ``fdtpu_jax_cache_misses_total`` /
  ``fdtpu_jax_cache_saved_seconds_total`` — the persistent compilation
  cache's own event stream (``/jax/compilation_cache/*``).  NOTE: a
  persistent-cache HIT still records a ``backend_compile_duration``
  event on this jax (the timer brackets compile-or-load), so "zero new
  compiles" is asserted as ``cache_misses == 0``, not as a zero compile
  counter.

Install is idempotent and process-global (JAX offers registration but
no deregistration); the listener holds only module state and costs one
dict lookup per COMPILE, i.e. nothing at steady state.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
import weakref
from typing import Callable, Optional

from . import spans
from .metrics import Registry, get_registry

__all__ = [
    "install",
    "installed",
    "mark_steady",
    "clear_steady",
    "steady_state",
    "compile_count",
    "compile_seconds",
    "cache_hits",
    "cache_misses",
    "compile_seconds_saved",
    "steady_recompiles",
]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

_lock = threading.Lock()
_installed = False
_steady = False
# the listener's targets: the process registry always, plus any
# registry a caller installed (a run with its own Observation registry).
# Weak: a finished run's private registry must not be fed forever.
_extra: "weakref.WeakSet[Registry]" = weakref.WeakSet()
_warn: Callable[[str], None] = lambda msg: print(msg, file=sys.stderr)

_COUNTERS = (
    ("fdtpu_jax_compiles_total", "XLA backend compiles"),
    ("fdtpu_jax_compile_seconds_total", "XLA backend compile seconds"),
    ("fdtpu_jax_steady_recompiles_total",
     "compiles observed AFTER steady state was declared "
     "(any nonzero value means something is recompiling)"),
    ("fdtpu_jax_cache_hits_total",
     "XLA compiles served from the persistent compilation cache"),
    ("fdtpu_jax_cache_misses_total",
     "XLA compiles the persistent compilation cache could not serve"),
    ("fdtpu_jax_cache_saved_seconds_total",
     "compile wall seconds skipped by persistent-cache hits"),
    ("fdtpu_jax_trace_seconds_total", "jaxpr trace seconds"),
)
_HELP = dict(_COUNTERS)


def _inc(name: str, amount: float = 1.0) -> None:
    for reg in (get_registry(), *_extra):
        reg.counter(name, _HELP[name]).inc(amount)


def _listener(event: str, duration: float, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _inc("fdtpu_jax_compiles_total")
        _inc("fdtpu_jax_compile_seconds_total", duration)
        # on the step timeline too, with the loader item during which it
        # fell: "which step recompiled" is one look
        now = time.perf_counter()
        item = spans.current_item()
        spans.get_tracer().record(
            "compile", now - duration, now,
            **({} if item is None else {"item": item, "parent": "item"}))
        if _steady:
            _inc("fdtpu_jax_steady_recompiles_total")
            _warn(
                f"obs.jaxmon: steady-state RECOMPILE ({duration:.2f}s) — "
                "an input shape/dtype or static argument changed after "
                "warmup; check bucket sizes and batch shapes"
            )
    elif event == TRACE_EVENT:
        _inc("fdtpu_jax_trace_seconds_total", duration)
    elif event == CACHE_SAVED_EVENT:
        _inc("fdtpu_jax_cache_saved_seconds_total", max(duration, 0.0))


def _event_listener(event: str, **kwargs) -> None:
    """Plain (non-duration) monitoring events: the persistent
    compilation cache's hit/miss stream."""
    if event == CACHE_HIT_EVENT:
        _inc("fdtpu_jax_cache_hits_total")
    elif event == CACHE_MISS_EVENT:
        _inc("fdtpu_jax_cache_misses_total")


def install(registry: Optional[Registry] = None,
            warn: Optional[Callable[[str], None]] = None) -> None:
    """Register the monitoring listener (idempotent — JAX has no
    listener deregistration, so the binding is process-lifetime).  The
    counters always land in the process registry; a ``registry`` passed
    here receives them too, for as long as it lives — never instead:
    which run installed first must not decide where every later run's
    compile counters go."""
    global _installed, _warn
    import jax.monitoring

    with _lock:
        if warn is not None:
            _warn = warn
        targets = [get_registry()]
        if registry is not None and registry is not targets[0]:
            _extra.add(registry)
            targets.append(registry)
        # pre-register so /metrics shows explicit zeros before the
        # first compile (absence would read as "not instrumented")
        for reg in targets:
            for name, help_ in _COUNTERS:
                reg.counter(name, help_)
        if _installed:
            return
        jax.monitoring.register_event_duration_secs_listener(_listener)
        jax.monitoring.register_event_listener(_event_listener)
        _installed = True


def installed() -> bool:
    return _installed


def mark_steady() -> None:
    """Declare warmup over: every compile from here on is a flagged
    (counted + warned) steady-state recompile."""
    global _steady
    install()
    _steady = True


def clear_steady() -> None:
    global _steady
    _steady = False


@contextlib.contextmanager
def steady_state():
    """``with jaxmon.steady_state():`` — flag recompiles inside the
    block (restores the previous flag on exit, so nesting composes)."""
    global _steady
    install()
    prev = _steady
    _steady = True
    try:
        yield
    finally:
        _steady = prev


def compile_count() -> float:
    return get_registry().value("fdtpu_jax_compiles_total")


def compile_seconds() -> float:
    return get_registry().value("fdtpu_jax_compile_seconds_total")


def cache_hits() -> float:
    return get_registry().value("fdtpu_jax_cache_hits_total")


def cache_misses() -> float:
    return get_registry().value("fdtpu_jax_cache_misses_total")


def compile_seconds_saved() -> float:
    return get_registry().value("fdtpu_jax_cache_saved_seconds_total")


def steady_recompiles() -> float:
    return get_registry().value("fdtpu_jax_steady_recompiles_total")
