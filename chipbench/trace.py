"""From a profiler trace to numbers: device busy time, idle share, the
whole steps the slice holds, with the first device's busy time, the
collectives' exposed time and the seconds of every kernel of the
program's own inside them, the operations that took most time and the
longest idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote with
nothing but jax (``ProfileData``; copied in idea from
``benchmarks/trace_analysis.py``, whose classification by fusion name is
not).  The reductions below take plain ``(name, start_ns, end_ns)``
tuples, so a test can check them on a trace written by hand.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
ALL_REDUCE = re.compile(r"^all-reduce")
#: the prefix the program gives each of its own kernels
#: (``ops/pallas_attention.py::KERNEL_NAMES``, ``ops/pallas_gmm.py``)
KERNEL_PREFIX = "fdtpu_"


def short_name(name: str) -> str:
    """The trace names an operation by its whole HLO instruction,
    ``%fusion.12 = (...) fusion(...)``: keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%").strip()[:120]


def kind_of(name: str) -> str:
    """``fusion.12`` and ``fusion.7`` are one kind: ``fusion``."""
    return re.sub(r"[.\d]+$", "", name) or name


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def load(directory: str) -> dict:
    """``{device id: {"ops": [...], "modules": [...]}}`` of
    ``(name, start_ns, end_ns)`` tuples, plus ``"lines"``: every plane's
    line names, for a reader that finds nothing where it looked."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(directory))
    out: dict = {"devices": {}, "lines": {}}
    for plane in data.planes:
        out["lines"][plane.name] = [ln.name for ln in plane.lines]
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            dev[key] = [(short_name(e.name), int(e.start_ns),
                         int(e.start_ns + e.duration_ns)) for e in line.events]
        out["devices"][int(m.group(1))] = dev
    return out


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: list = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The part of the merged intervals ``a`` that no interval of the
    merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(devices: dict):
    """First start and last end of any operation on any device."""
    starts = [ev[1] for d in devices.values() for ev in d["ops"]]
    ends = [ev[2] for d in devices.values() for ev in d["ops"]]
    if not starts:
        return None
    return min(starts), max(ends)


def intersect(a, b) -> list:
    """The part of the merged intervals ``a`` that the merged ``b``
    covers."""
    return subtract(a, subtract(a, b))


def step_cycles(runs, win) -> list:
    """The whole steps of the slice ``win``, as intervals: each run of
    the step program that starts after the slice's first operation, from
    its start to the next run's start, so the device's work between two
    runs counts with the step before it.  The first run may be one the
    session cut (the profiler records it clipped to the session: 668 of
    a 712 ms step, PERF.md, PR 37) and the last has no next: neither
    counts, so a kernel's seconds and a step's work are read over the
    same whole runs, whatever their lengths and wherever the cut falls."""
    starts = sorted(s for s, _ in runs)
    return [(a, b) for a, b in zip(starts, starts[1:]) if a > win[0]]


def reduce(trace: dict) -> dict | None:
    """The numbers the per-layer readers take.  None where no operation
    ran on a device in the trace."""
    devices = trace["devices"]
    win = window_of(devices)
    if win is None:
        return None
    window_ns = win[1] - win[0]
    busy = {i: union((s, e) for _, s, e in d["ops"]) for i, d in devices.items()}
    busy_ns = {i: total(b) for i, b in busy.items()}
    first = min(devices)
    d0 = devices[first]
    # the step is the module that took most time on the first device
    by_module: dict = {}
    for name, s, e in d0["modules"]:
        by_module.setdefault(name, []).append((s, e))
    step_module, cycles = None, []
    if by_module:
        step_module = max(by_module, key=lambda n: total(by_module[n]))
        cycles = step_cycles(by_module[step_module], win)
    coll = union((s, e) for n, s, e in d0["ops"] if ALL_REDUCE.match(n))
    other = union((s, e) for n, s, e in d0["ops"] if not COLLECTIVE.match(n))
    exposed_ns = total(intersect(subtract(coll, other), cycles))
    by_op: dict = {}
    for name, s, e in d0["ops"]:
        by_op[kind_of(name)] = by_op.get(kind_of(name), 0) + (e - s)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    kernels = {n: total(intersect(union((s, e) for m, s, e in d0["ops"]
                                        if kind_of(m) == n), cycles)) / 1e9
               for n in by_op if n.startswith(KERNEL_PREFIX)}
    # idle gaps on the first device, named by the operation that ended them
    ops_sorted = sorted(d0["ops"], key=lambda ev: ev[1])
    gaps, edge = [], win[0]
    for name, s, e in ops_sorted:
        if s > edge:
            gaps.append((f"before {kind_of(name)}", s - edge))
        edge = max(edge, e)
    gap_by_name: dict = {}
    for name, g in gaps:
        gap_by_name[name] = gap_by_name.get(name, 0) + g
    top_gaps = sorted(gap_by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns.values()) / len(busy_ns) / 1e9,
        "busy0_s": busy_ns[first] / 1e9,
        "steps": len(cycles),
        "steps_busy0_s": total(intersect(busy[first], cycles)) / 1e9,
        "step_module": step_module,
        "has_all_reduce": bool(coll),
        "allreduce_exposed_s": exposed_ns / 1e9,
        "kernels": kernels,
        "device_ops": [[n, t / 1e9] for n, t in top],
        "idle_gaps": [[n, t / 1e9] for n, t in top_gaps],
    }
