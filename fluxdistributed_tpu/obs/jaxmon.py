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
  (:mod:`.spans`), carrying the span and loader item during which it
  fell, the program's ``fun_name`` as jax names it, ``cache``
  (``"hit"``: the persistent cache served it and the span is the fetch
  and load; ``"miss"``: it had it not, so the program compiled and was
  written there; ``"off"``: the cache neither served nor kept it: no
  directory, or a program it does not store) and, on a hit, ``load_s``:
  which program missed the cache, and from where, is one look at the
  ring;
* ``fdtpu_jax_trace_seconds_total`` — jaxpr tracing time (host-side
  program construction, distinct from XLA compile time), each
  outermost trace also a ``trace`` span with its ``fun_name`` (a jitted
  function traced inside another's trace is inside that one's span and
  seconds, not counted again); each lowering to an MLIR module a
  ``lower`` span the same way;
* ``fdtpu_jax_steady_recompiles_total`` — compiles that happened AFTER
  the caller declared steady state.  The serve engine's "ONE decode
  compile" invariant (tests assert it offline) becomes a live metric:
  scrape nonzero here in production and something is recompiling.
* ``fdtpu_jax_cache_hits_total`` / ``fdtpu_jax_cache_misses_total`` /
  ``fdtpu_jax_cache_saved_seconds_total`` — the persistent compilation
  cache's own event stream (``/jax/compilation_cache/*``), with
  ``fdtpu_jax_cache_load_seconds_total``, the seconds the hits took to
  fetch and load.  NOTE: a
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
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_lock = threading.Lock()
_installed = False
_steady = False
# the listener's targets: the process registry always, plus any
# registry a caller installed (a run with its own Observation registry).
# Weak: a finished run's private registry must not be fed forever.
_extra: "weakref.WeakSet[Registry]" = weakref.WeakSet()
_warn: Callable[[str], None] = lambda msg: print(msg, file=sys.stderr)


class _Thread(threading.local):
    """What this thread's events said since its last ``compile`` span.
    jax reports the cache's part from inside the compile-or-load bracket
    and the bracket's seconds when it closes, all on the compiling
    thread; traces nest, and only the outermost is a span."""

    cache = "off"
    load_s = None
    trace_depth = 0


_thread = _Thread()

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
    ("fdtpu_jax_cache_load_seconds_total",
     "wall seconds fetching and loading persistent-cache hits"),
    ("fdtpu_jax_trace_seconds_total", "jaxpr trace seconds"),
)
_HELP = dict(_COUNTERS)


def _inc(name: str, amount: float = 1.0) -> None:
    for reg in (get_registry(), *_extra):
        reg.counter(name, _HELP[name]).inc(amount)


def _record(name: str, duration: float, fun_name, **args) -> None:
    """A span for a bracket jax has just closed, with the span and the
    loader item during which it fell."""
    now = time.perf_counter()
    if fun_name is not None:
        args["fun_name"] = str(fun_name)
    spans.get_tracer().record(name, now - duration, now,
                              **spans.enclosing(), **args)


def _listener(event: str, duration: float, fun_name=None, **kwargs) -> None:
    if event == BACKEND_COMPILE_EVENT:
        _inc("fdtpu_jax_compiles_total")
        _inc("fdtpu_jax_compile_seconds_total", duration)
        # on the step timeline too: "which step recompiled", "which
        # program missed the cache, and from where" are one look
        args = {"cache": _thread.cache}
        if _thread.load_s is not None:
            args["load_s"] = _thread.load_s
        _thread.cache, _thread.load_s = "off", None
        _record("compile", duration, fun_name, **args)
        if _steady:
            _inc("fdtpu_jax_steady_recompiles_total")
            _warn(
                f"obs.jaxmon: steady-state RECOMPILE ({duration:.2f}s) — "
                "an input shape/dtype or static argument changed after "
                "warmup; check bucket sizes and batch shapes"
            )
    elif event == TRACE_EVENT:
        # a trace begun before the listeners were installed closes here
        # with no opening counted: never below the outermost
        _thread.trace_depth = max(_thread.trace_depth - 1, 0)
        if _thread.trace_depth == 0:
            _inc("fdtpu_jax_trace_seconds_total", duration)
            _record("trace", duration, fun_name)
    elif event == LOWER_EVENT:
        _record("lower", duration, fun_name)
    elif event == CACHE_SAVED_EVENT:
        _inc("fdtpu_jax_cache_saved_seconds_total", max(duration, 0.0))
    elif event == CACHE_LOAD_EVENT:
        _inc("fdtpu_jax_cache_load_seconds_total", duration)
        _thread.load_s = duration


def _event_listener(event: str, **kwargs) -> None:
    """Plain (non-duration) monitoring events: the persistent
    compilation cache's hit/miss stream."""
    if event == CACHE_HIT_EVENT:
        _inc("fdtpu_jax_cache_hits_total")
        _thread.cache = "hit"
    elif event == CACHE_MISS_EVENT:
        _inc("fdtpu_jax_cache_misses_total")
        _thread.cache = "miss"


def _scalar_listener(event: str, value: float, **kwargs) -> None:
    """jax reports a bracket's opening as a scalar under the name its
    closing will carry."""
    if event == TRACE_EVENT:
        _thread.trace_depth += 1


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
        jax.monitoring.register_scalar_listener(_scalar_listener)
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
