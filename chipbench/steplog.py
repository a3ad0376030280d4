"""From the program's own step timeline to numbers: in which host phase
the loop sat while the device held no step, what a worker's assembly of
a batch took, and how evenly steps completed.

The program keeps one span ring (``fluxdistributed_tpu.obs.get_tracer``)
and fills it on every run, traced or not.  Per loader item ``j`` it
holds an ``item`` span on the loop's thread with ``data_wait`` and
``dispatch`` inside, ``assemble`` and ``h2d`` from a prefetch worker and
a ``device`` span from the later of ``dispatch``'s end and item ``j-1``'s
completion to item ``j``'s completion, each with ``args.item == j``.
The reductions below take such events as plain dicts (Chrome trace
events: ``name``, ``ts`` and ``dur`` in microseconds, ``args``), so a
test checks them on events written by hand.

The union of the ``device`` spans is the time in which the host had
handed the device a step, so the starved shares say for how long, and in
which phase of the loop, the host left the device without work.  That is
not the profiler's idle share, and bounds it from neither side: a step
may begin on the device before its dispatch returns (then the shares
read high), and a step that is held is not executing all the while
(launch latency, a wait for its batch's copy; then they read low).
"""

from __future__ import annotations

import math

from .trace import subtract, total, union

MIN_ITEMS = 20  # fewer items than this give no number
PHASES = ("data_wait", "dispatch")


def program_events():
    """The span ring of the program in this process; None where the
    program keeps no process-wide tracer."""
    from fluxdistributed_tpu import obs

    get_tracer = getattr(obs, "get_tracer", None)
    return None if get_tracer is None else get_tracer().trace_events()


def _ends(ev):
    return ev["ts"] / 1e6, (ev["ts"] + ev["dur"]) / 1e6


def window_items(events, n_items: int) -> list:
    """Of the last ``n_items`` loader items the loop dispatched, in the
    order of their dispatch, those before the first one opened while a
    profiler session recorded (``traced``).  The session stalls the loop
    for a second or two, the prefetch buffer fills meanwhile, and the
    loop then runs in another regime for seconds after the session has
    ended (PERF.md, PR 24): only what came before is the loop that the
    end-to-end runs measure.  Where the ring holds an id twice, the
    later span counts.

    Each is ``{"item", "span", "data_wait", "dispatch", "assemble",
    "done"}``: ``(start, end)`` seconds or None, ``done`` the completion
    time."""
    last: dict = {}
    for ev in events:
        item = (ev.get("args") or {}).get("item")
        if item is not None:
            last[ev["name"], item] = ev
    dispatched = sorted(
        (ev["ts"], item) for (name, item), ev in last.items()
        if name == "dispatch" and ("item", item) in last)
    out = []
    for _, item in dispatched[-n_items:] if n_items > 0 else []:
        if last["item", item]["args"].get("traced"):
            break
        row = {"item": item, "span": _ends(last["item", item])}
        for name in PHASES + ("assemble",):
            ev = last.get((name, item))
            row[name] = _ends(ev) if ev else None
        dev = last.get(("device", item))
        row["done"] = _ends(dev)[1] if dev else None
        out.append(row)
    return out


def held(events) -> list:
    """Merged intervals in which the device had been handed a step: the
    union of every ``device`` span."""
    return union(_ends(ev) for ev in events if ev["name"] == "device")


def reduce(events, n_items: int) -> dict | None:
    """The five numbers over the window's items before any profiler
    session; None where there are fewer than ``MIN_ITEMS`` of them."""
    items = window_items(events, n_items)
    if len(items) < MIN_ITEMS:
        return None
    busy = held(events)
    wall = sum(e - s for s, e in (it["span"] for it in items))
    starved = {p: 0.0 for p in PHASES + ("other",)}
    for it in items:
        # the phases lie inside the item's span and apart from each other
        in_phase = {p: total(subtract([it[p]], busy)) for p in PHASES if it[p]}
        for p, seconds in in_phase.items():
            starved[p] += seconds
        starved["other"] += (total(subtract([it["span"]], busy))
                             - sum(in_phase.values()))
    assembled = [e - s for s, e in (it["assemble"] for it in items
                                    if it["assemble"])]
    # completions of two items that follow each other (a skipped batch
    # leaves a gap in the ids, and a gap is no interval)
    intervals = sorted(
        b["done"] - a["done"] for a, b in zip(items, items[1:])
        if b["item"] == a["item"] + 1 and a["done"] and b["done"])
    out = {f"starved_{p}_pct": 100.0 * starved[p] / wall for p in starved}
    out["items"] = len(items)
    out["assemble_ms"] = (1e3 * sum(assembled) / len(assembled)
                          if assembled else None)
    out["step_intervals"] = len(intervals)
    out["step_interval_p95_ms"] = (
        1e3 * intervals[math.ceil(0.95 * len(intervals)) - 1]
        if len(intervals) >= MIN_ITEMS else None)
    out["step_interval_p50_ms"] = (
        1e3 * intervals[len(intervals) // 2] if intervals else None)
    return out


def read(ctx: dict, name: str):
    """One of ``reduce``'s numbers for the run that ``ctx`` describes,
    from the program's ring as it stands after the window."""
    events = program_events()
    if events is None:
        return None
    n_items = ctx["window"]["steps"] // ctx["traffic"]["steps_per_call"]
    numbers = reduce(events, n_items)
    return None if numbers is None else numbers[name]
