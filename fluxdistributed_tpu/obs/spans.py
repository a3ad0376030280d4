"""The step timeline: one process-wide, always-on, bounded span tracer.

``jax.profiler`` answers "what did the DEVICE do" at ~GB trace cost for
a fixed window; this tracer answers "where did the HOST loop's time go,
and did the device starve meanwhile" continuously and for pennies.  The
trainer opens an ``item`` span per loader item with ``data_wait`` /
``dispatch`` / ``eval`` / ``checkpoint`` children, the prefetch workers
record ``assemble`` and ``h2d``, a :class:`CompletionWatcher` closes a
``device`` span when the item's step has really finished (with
``ahead``: how far the loop had run ahead of the device), and
:mod:`.jaxmon` records every ``trace``, ``lower`` and backend
``compile`` with the program's name and what the compile cache did.
All spans of one loader item carry its ``item`` id and the ``parent``
that caused them, so a timeline reader joins them without guessing.
The set-up lies on the same timeline: ``prepare_training`` is a
``prepare`` span with a child for each phase that ran (``cache_enable``,
``model_init``, ``step_build``, ``aot``, ``warmup``), each call of
``train`` a ``train`` span that its items name as their parent.

The same brackets also open ``jax.profiler`` annotations
(``fdtpu/<name>`` with the ``item`` stat; the ``item`` span is a
``StepTraceAnnotation``), so a profiler session shows them in its
``/host:CPU`` plane beside the device planes and on their clock.  With
no session recording an annotation costs well under a microsecond.

Cost discipline: a span appends one small tuple to a bounded ring under
a lock only at span END; timestamps come from ``perf_counter``.  The
ring (default 20,000 spans, more than 2,500 loader items) stays flat
over a days-long run; ``export_chrome_trace`` writes it as Chrome
trace-event JSON for ``chrome://tracing`` or ui.perfetto.dev.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import queue
import threading
import time
from collections import deque
from typing import Callable, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = [
    "CompletionWatcher",
    "SpanTracer",
    "current_span",
    "enclosing",
    "get_tracer",
    "innermost_active",
]

# the open spans of this context, innermost last; contextvars give
# correct nesting across threads AND async contexts
_stack: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "fdtpu_span_stack", default=()
)


def current_span() -> Optional[str]:
    """Innermost open span name in the calling context, or ``None``."""
    s = _stack.get()
    return s[-1].name if s else None


# -- cross-thread active-span registry -------------------------------------
# The contextvar above answers "where am I" for the CALLING context; the
# stall watchdog needs "where is the LOOP" from its own daemon thread.
# Every open span also registers here: {thread_id: [(seq, span), ...]},
# where seq is a global open-order counter so "innermost" is well-defined
# across threads.  One small lock + list op per span — phases tick a
# handful of times per step, never per token.
_active_lock = threading.Lock()
_active: dict = {}
_active_seq = itertools.count(1)


def _active_push(span: "_Span") -> None:
    tid = threading.get_ident()
    with _active_lock:
        _active.setdefault(tid, []).append((next(_active_seq), span))


def _active_pop() -> None:
    tid = threading.get_ident()
    with _active_lock:
        stack = _active.get(tid)
        if stack:
            stack.pop()
        if not stack:
            _active.pop(tid, None)


def _newest_active(want=lambda span: True) -> Optional["_Span"]:
    with _active_lock:
        newest, found = 0, None
        for stack in _active.values():
            for seq, span in reversed(stack):
                if want(span):
                    if seq > newest:
                        newest, found = seq, span
                    break
    return found


def innermost_active() -> Optional[str]:
    """Name of the most recently OPENED still-open span across all
    threads, or ``None`` — what the stall watchdog reports as "where the
    loop is wedged" (a stalled step is, by definition, inside whichever
    bracket opened last and never closed)."""
    span = _newest_active()
    return span.name if span is not None else None


def enclosing() -> dict:
    """``parent`` and ``item`` for a span reported from outside any
    bracket (a compile, a trace, a lowering: :mod:`.jaxmon` learns of
    each when it is over): the innermost open span of the calling
    context and its loader item, else the newest open item anywhere."""
    s = _stack.get()
    if s:
        inner = s[-1]
        return {"parent": inner.name,
                **({} if inner.item is None else {"item": inner.item})}
    span = _newest_active(lambda sp: sp.item is not None)
    return {} if span is None else {"parent": "item", "item": span.item}


class _Span:
    __slots__ = ("_tracer", "name", "args", "item", "_t0", "_token", "_note")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.item = args.get("item")

    def __enter__(self):
        stack = _stack.get()
        if stack:
            # a child names the span that caused it and works for the
            # same loader item unless it says otherwise
            parent = stack[-1]
            self.args.setdefault("parent", parent.name)
            if self.item is None and parent.item is not None:
                self.item = self.args["item"] = parent.item
        self._token = _stack.set(stack + (self,))
        _active_push(self)
        if self.name == "item":
            self._note = StepTraceAnnotation("fdtpu/item", step_num=self.item)
        elif self.item is not None:
            self._note = TraceAnnotation("fdtpu/" + self.name, item=self.item)
        else:
            self._note = TraceAnnotation("fdtpu/" + self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        _active_pop()
        _stack.reset(self._token)
        self._tracer.record(self.name, self._t0, t1, **self.args)
        return False


class SpanTracer:
    """Collects spans in a bounded ring; exports Chrome trace-event JSON.

    ``max_events`` is the ring's capacity; the oldest spans drop first
    (a days-long run must not grow host memory without bound).
    """

    def __init__(self, max_events: int = 20_000):
        self._events: deque = deque(maxlen=max_events)
        self._lock = threading.Lock()
        # trace-event ts fields are µs relative to this origin; pairing
        # with wall time lets readers line the trace up with log stamps
        self._origin = time.perf_counter()
        self._origin_unix = time.time()
        self.dropped = 0

    def span(self, name: str, **args):
        """``with tracer.span("data_wait"):`` — bracket one phase.  An
        ``item=`` argument is the loader item the span works for;
        nested spans inherit it and record their ``parent``."""
        return _Span(self, name, args)

    def record(self, name: str, t0: float, t1: float, **args) -> None:
        """A span whose ends were taken elsewhere (``perf_counter``
        seconds): a step's completion, a compile reported afterwards."""
        ev = (name, t0, t1, threading.get_ident() & 0x7FFFFFFF, args)
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def trace_events(self) -> list:
        """The Chrome trace-event list (JSON-ready dicts, in the order
        the spans ENDED)."""
        with self._lock:
            events = list(self._events)
        out = []
        for name, t0, t1, tid, args in events:
            ev = {
                "name": name,
                "ph": "X",  # complete event: begin ts + dur in one record
                "ts": (t0 - self._origin) * 1e6,
                "dur": (t1 - t0) * 1e6,
                "pid": 0,
                "tid": tid,
                "cat": "fdtpu",
            }
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def export_chrome_trace(self, path: str) -> int:
        """Write the buffer as a Chrome/Perfetto trace-event JSON file;
        returns the number of events written.

        The JSON Object Format (``{"traceEvents": [...]}``) is used
        rather than the bare array so metadata rides along; both load in
        chrome://tracing and Perfetto.
        """
        events = self.trace_events()
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "origin_unix_time": self._origin_unix,
                "dropped_events": self.dropped,
                "producer": "fluxdistributed_tpu.obs.spans",
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(events)


_TRACER = SpanTracer()


def get_tracer() -> SpanTracer:
    """The process-wide tracer — what the trainer loop, the prefetch
    workers and the compile listener share, beside ``get_registry()``."""
    return _TRACER


class CompletionWatcher:
    """Stamps when each loader item's step really finished, without
    blocking the loop that dispatched it.

    The loop hands over ``(item, value, dispatched)`` in order, where
    ``value`` is the step's small output (its metrics, never the state)
    and ``dispatched`` the ``perf_counter`` time its dispatch returned.
    One daemon thread waits on each value in turn and records a
    ``device`` span from the later of ``dispatched`` and the item
    before's completion to this item's completion: the time in which the
    device had been handed the item and not yet finished it.  An error that surfaces at completion
    is recorded on the span and never raised into the loop.
    ``on_value`` gets each completed value in the same thread (the
    trainer feeds a router's counters from the step's metrics there).

    Each ``device`` span also says how far the loop ran ahead of the
    device: ``ahead`` is the number of items handed over before this one
    and not yet complete when this one was handed over (0 for a loop in
    lockstep with the device).  ``on_ahead`` gets the same number in the
    caller's thread (the trainer sets a gauge with it).
    """

    def __init__(self, tracer: SpanTracer,
                 on_done: Optional[Callable[[float], None]] = None,
                 on_value: Optional[Callable[[object], None]] = None,
                 on_ahead: Optional[Callable[[int], None]] = None):
        self._tracer = tracer
        self._on_done = on_done
        self._on_value = on_value
        self._on_ahead = on_ahead
        # each written by one thread only (the caller's, the watcher's)
        # and read by the other: a count a moment old is a count
        self._handed = 0
        self._closed = 0
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, name="fdtpu-completion-watcher", daemon=True)
        self._thread.start()

    def watch(self, item: int, value, dispatched: float) -> None:
        ahead = self._handed - self._closed
        self._handed += 1
        if self._on_ahead is not None:
            self._on_ahead(ahead)
        self._queue.put((item, value, dispatched, ahead))

    def _run(self) -> None:
        import jax

        last_done = 0.0
        while True:
            job = self._queue.get()
            if job is None:
                return
            item, value, dispatched, ahead = job
            args = {"item": item, "parent": "dispatch", "ahead": ahead}
            try:
                jax.block_until_ready(value)
            except Exception as e:  # noqa: BLE001 - the loop meets it itself
                args["error"] = f"{type(e).__name__}: {e}"[:500]
            done = time.perf_counter()
            self._closed += 1
            if self._on_value is not None and "error" not in args:
                # the value is ready: reading it here waits for nothing
                try:
                    self._on_value(value)
                except Exception as e:  # noqa: BLE001 - never into the loop
                    args["error"] = f"{type(e).__name__}: {e}"[:500]
            del job, value
            start = max(dispatched, last_done)
            self._tracer.record("device", start, done, **args)
            if self._on_done is not None:
                self._on_done(done - start)
            last_done = done

    def close(self, timeout: float = 60.0) -> bool:
        """Wait, at most ``timeout`` seconds, for the items handed over
        to complete; whether they all did."""
        self._queue.put(None)
        self._thread.join(timeout)
        return not self._thread.is_alive()
