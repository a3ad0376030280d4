"""Cold start by phase, and the loop's lead over the device, from the
program's own timeline (no metric of its own: the ``setup_*`` readers
and ``loop_ahead_steps`` beside it load this file by path).

Beside the loop's spans (``steplog.py``) the program's ring holds the
set-up: a ``prepare`` span around ``prepare_training`` with a child for
each phase that ran (``cache_enable``, ``model_init``, ``step_build``,
``aot``, ``warmup``), a ``train`` span around each call of ``train()``,
and from jax's own reports a ``trace``, a ``lower`` and a ``compile``
span for every program, the last with ``cache`` (``"hit"``, ``"miss"``
or ``"off"``), ``fun_name`` and, on a hit, ``load_s``.  A ``device``
span says in ``ahead`` how many items the loop had handed over and not
yet seen complete when it handed that one over.

The window's call of ``train()`` is the last ``train`` span of the ring;
the set-up is what this run did before that span began, and a run began
``ctx["setup_s"]`` before its window (a process that ran a cell before,
as the tests do, still holds that run's spans).  The reductions take the
events as plain dicts, so a test checks them on events written by hand;
on a program without a ``train`` span they find nothing and give None.
"""

from __future__ import annotations

import json

from chipbench import steplog
from chipbench.trace import total, union


def _ends(ev):
    return ev["ts"] / 1e6, (ev["ts"] + ev["dur"]) / 1e6


def _arg(ev, key):
    return (ev.get("args") or {}).get(key)


def set_up(events, setup_s: float):
    """This run's spans that ended before its window's ``train`` span
    began; None without such a span."""
    calls = [e["ts"] for e in events if e["name"] == "train"]
    if not calls:
        return None
    t1 = max(calls) / 1e6
    # a run before this one ended before this one began, which was no
    # later than setup_s before the window
    t0 = t1 - setup_s
    return [e for e in events if t0 < _ends(e)[1] <= t1]


def seconds(setup, *names) -> float:
    """Seconds covered by the set-up's spans of these names (their
    union: a trace may lie inside a lowering)."""
    return total(union(_ends(e) for e in setup if e["name"] in names))


def first_step_s(setup):
    """From the start of the run's first ``train`` span to the end of
    the first ``device`` span inside it: trace, lower, compile or load,
    run."""
    calls = sorted((e for e in setup if e["name"] == "train"),
                   key=lambda e: e["ts"])
    if not calls:
        return None
    t0, t1 = _ends(calls[0])
    done = [_ends(e)[1] for e in setup
            if e["name"] == "device" and t0 <= _ends(e)[1] <= t1]
    return min(done) - t0 if done else None


def missed(setup) -> list:
    """The set-up's compiles that the cache could not serve, most
    seconds first: ``[fun_name, parent, seconds]``."""
    rows = [[_arg(e, "fun_name"), _arg(e, "parent"), e["dur"] / 1e6]
            for e in setup
            if e["name"] == "compile" and _arg(e, "cache") == "miss"]
    return sorted(rows, key=lambda r: -r[2])


def outside_program_s(setup, setup_s: float):
    """What of ``setup_s`` lies in no ``prepare`` and no ``train`` span:
    imports, the backend, the pool, the benchmark's seeded weights and
    its recorder.  None where the ring no longer holds ``prepare``."""
    if not any(e["name"] == "prepare" for e in setup):
        return None
    return setup_s - sum(e["dur"] for e in setup
                         if e["name"] in ("prepare", "train")) / 1e6


def ahead_steps(events, n_items: int, steps_per_call: int):
    """Mean ``ahead`` over the ``device`` spans of the window's items
    before any profiler session, in optimizer steps."""
    items = {row["item"] for row in steplog.window_items(events, n_items)}
    last = {}
    for e in events:
        if e["name"] == "device" and _arg(e, "item") in items:
            last[_arg(e, "item")] = _arg(e, "ahead")
    ahead = [a for a in last.values() if a is not None]
    return steps_per_call * sum(ahead) / len(ahead) if ahead else None


def read(ctx: dict, name: str):
    """One of the eight numbers for the run that ``ctx`` describes, from
    the program's ring as it stands after the window."""
    events = steplog.program_events()
    if events is None:
        return None
    if name == "loop_ahead_steps":
        spc = ctx["traffic"]["steps_per_call"]
        return ahead_steps(events, ctx["window"]["steps"] // spc, spc)
    setup = set_up(events, ctx["setup_s"])
    if setup is None:
        return None
    if name == "setup_model_init_s":
        return seconds(setup, "model_init")
    if name == "setup_warmup_s":
        return seconds(setup, "warmup", "aot")
    if name == "setup_first_step_s":
        return first_step_s(setup)
    if name == "setup_trace_lower_s":
        return seconds(setup, "trace", "lower")
    if name == "setup_cache_load_s":
        return sum(_arg(e, "load_s") or 0.0 for e in setup
                   if e["name"] == "compile")
    if name == "setup_cache_misses":
        rows = missed(setup)
        if rows:
            # which programs, and from where: the run's notes name them
            print("chipbench notes " + json.dumps(
                {"setup_cache_misses": rows[:12]}), flush=True)
        return len(rows)
    if name == "setup_outside_program_s":
        return outside_program_s(setup, ctx["setup_s"])
    raise KeyError(name)
