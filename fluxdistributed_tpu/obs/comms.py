"""Collective-traffic ledger: what a compiled step moves over the
interconnect, counted per step per mesh axis.

Every parallelism variant's scaling story is a claim about collectives
— plain DP all-reduces the gradients, ZeRO-1 (arXiv:2004.13336)
replaces that with reduce-scatter + all-gather so the update shards,
pipeline stages ``ppermute`` activations, Ulysses/MoE ``all_to_all``
tokens — but nothing in the repo ever MEASURED those claims.  This
ledger does, at two layers that together cover every variant:

* **jaxpr layer** (:func:`jaxpr_collectives`) — walk the traced
  program (recursing through pjit/scan/cond/while/shard_map/custom-vjp
  sub-jaxprs) counting the explicit collective primitives ``psum`` /
  ``psum_scatter`` / ``all_gather`` / ``all_to_all`` / ``ppermute``
  with their mesh axes straight off the equation params and buffer
  bytes off the avals.  This is the SEMANTIC truth of explicitly-
  written schedules (shard_map variants, the pipeline scan) — e.g. the
  ZeRO-1 shard_map step shows reduce-scatter + all-gather on the
  ``data`` axis where the DP step shows only all-reduce, the paper's
  signature, asserted exactly on the 8-virtual-device CPU mesh.
* **HLO layer** (:func:`hlo_collectives`) — parse the
  post-optimization HLO of the COMPILED executable, where GSPMD
  variants (``spmd="jit"`` DP, fsdp, tp) materialize the collectives
  XLA inserted for them (their jaxprs contain none).  Mesh axes are
  recovered by matching each op's ``replica_groups`` against the
  partitions each axis combination induces on the mesh.

Counting semantics (both layers report PER STEP): a ``scan`` body's
collectives multiply by the trip count; ``cond`` branches merge at the
per-entry MAX (an upper bound — one branch runs per invocation);
``while`` bodies count once (trip count unknowable statically — a
documented lower bound).  Bytes are the collective's buffer size (max
of operand/result bytes — all-gather outputs and reduce-scatter inputs
are the full buffer), not wire bytes: ring-algorithm wire traffic is
``(N-1)/N ×`` buffer per hop and depends on the backend's algorithm
choice, which a static ledger should not guess.

The ledger feeds the ``fdtpu-profile/v2`` artifact next to the memory
model (:mod:`.memstats` compiles each variant once and hands the same
executable to both) and ``bin/fit.py``'s report.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "collective_signature",
    "hlo_collectives",
    "jaxpr_collectives",
    "merge_entries",
    "total_bytes",
]

#: jaxpr primitive name → canonical collective kind (the HLO spelling
#: without dashes, so both layers key identically)
JAXPR_COLLECTIVES = {
    "psum": "all_reduce",
    # what psum of a varying value traces to under check_vma=True
    "psum_invariant": "all_reduce",
    "pmin": "all_reduce",
    "pmax": "all_reduce",
    "psum_scatter": "reduce_scatter",
    "reduce_scatter": "reduce_scatter",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "all_to_all": "all_to_all",
    "ppermute": "ppermute",
    "pshuffle": "ppermute",
}

#: HLO opcode → canonical kind
HLO_COLLECTIVES = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "collective-permute": "ppermute",
}

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    import jax.numpy as jnp

    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:  # polymorphic dim — skip, bytes stay honest-0
            return 0
    return n * jnp.dtype(dtype).itemsize


def _eqn_axes(eqn) -> Optional[Tuple[str, ...]]:
    """The mesh axis names a collective equation runs over (None when
    the primitive carries none — e.g. a constant-folded psum)."""
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", None)
    if axes is None:
        return None
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    named = tuple(str(a) for a in axes if isinstance(a, str))
    return named or None


def _sub_jaxprs(eqn):
    """Every sub-jaxpr in an equation's params (pjit jaxpr, scan body,
    cond branches, while cond/body, custom-vjp call_jaxpr, remat, ...),
    labeled so branch alternatives can merge at max instead of sum."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def _as_jaxpr(v):
        if isinstance(v, ClosedJaxpr):
            return v.jaxpr
        if isinstance(v, Jaxpr):
            return v
        return None

    branches, bodies = [], []
    for key, v in eqn.params.items():
        j = _as_jaxpr(v)
        if j is not None:
            bodies.append(j)
            continue
        if isinstance(v, (tuple, list)):
            subs = [s for s in (_as_jaxpr(b) for b in v) if s is not None]
            if not subs:
                continue
            if key == "branches":
                branches.extend(subs)
            else:
                bodies.extend(subs)
    return bodies, branches


_Key = Tuple[str, Optional[Tuple[str, ...]]]


def _merge_max(dst: Dict[_Key, dict], src: Dict[_Key, dict]) -> None:
    for k, v in src.items():
        cur = dst.get(k)
        if cur is None or (v["count"], v["bytes"]) > (cur["count"],
                                                      cur["bytes"]):
            dst[k] = v


def _walk(jaxpr, mult: int, acc: Dict[_Key, dict]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        kind = JAXPR_COLLECTIVES.get(name)
        if kind is not None:
            per_call = max(
                [_aval_bytes(v.aval) for v in
                 list(eqn.invars) + list(eqn.outvars)] or [0])
            key = (kind, _eqn_axes(eqn))
            cell = acc.setdefault(
                key, {"count": 0, "bytes": 0, "bytes_per_call": per_call})
            cell["count"] += mult
            cell["bytes"] += mult * per_call
            cell["bytes_per_call"] = max(cell["bytes_per_call"], per_call)
        bodies, branches = _sub_jaxprs(eqn)
        sub_mult = mult
        if name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        for b in bodies:
            _walk(b, sub_mult, acc)
        if branches:
            # one branch executes per invocation: merge alternatives at
            # the per-entry max (upper bound), never the sum — the
            # cond-skipped pipeline chunks would otherwise double-count
            merged: Dict[_Key, dict] = {}
            for b in branches:
                one: Dict[_Key, dict] = {}
                _walk(b, sub_mult, one)
                _merge_max(merged, one)
            for k, v in merged.items():
                cell = acc.setdefault(
                    k, {"count": 0, "bytes": 0, "bytes_per_call": 0})
                cell["count"] += v["count"]
                cell["bytes"] += v["bytes"]
                cell["bytes_per_call"] = max(cell["bytes_per_call"],
                                             v["bytes_per_call"])


def _entries(acc: Dict[_Key, dict]) -> List[dict]:
    out = []
    for (kind, axes), cell in sorted(
            acc.items(), key=lambda kv: (kv[0][0], kv[0][1] or ())):
        out.append({
            "kind": kind,
            "axes": list(axes) if axes else None,
            "count": int(cell["count"]),
            "bytes": int(cell["bytes"]),
            "bytes_per_call": int(cell["bytes_per_call"]),
        })
    return out


def jaxpr_collectives(fn, args: Tuple[Any, ...]) -> List[dict]:
    """Static per-step collective ledger of ``fn`` at ``args`` from the
    traced jaxpr (see module doc for counting semantics).  Entries::

        {"kind": "all_reduce" | "all_gather" | "reduce_scatter" |
                 "all_to_all" | "ppermute",
         "axes": ["data"] | None,   # mesh axes, None = not recorded
         "count": N,                # calls per step
         "bytes": B,                # Σ buffer bytes over those calls
         "bytes_per_call": B1}      # largest single buffer

    GSPMD-partitioned programs (``spmd="jit"`` dp, fsdp, tp) trace to
    jaxprs with NO explicit collectives — XLA inserts them at compile
    time; use :func:`hlo_collectives` on the compiled executable for
    those.  Tracing is abstract: nothing executes, nothing compiles."""
    import jax

    closed = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    acc: Dict[_Key, dict] = {}
    _walk(closed.jaxpr, 1, acc)
    return _entries(acc)


# -- HLO layer --------------------------------------------------------------

_HLO_OP_RE = re.compile(
    r"=\s*(?P<type>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<suffix>-start|-done)?\(")
_HLO_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_HLO_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
# iota form: [G,K]<=[d0,d1,...] optionally T(perm) — arange(prod(dims))
# reshaped to dims, transposed by perm, flattened, dealt into G rows of K
_HLO_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _HLO_SHAPE_RE.findall(type_str):
        size = _HLO_DTYPE_BYTES.get(dtype)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def _parse_groups(line: str,
                  nworld: int = 0) -> Optional[List[Tuple[int, ...]]]:
    m = _HLO_GROUPS_RE.search(line)
    if m:
        return [tuple(int(x) for x in grp.split(",") if x)
                for grp in re.findall(r"\{([^}]*)\}", m.group(1))]
    m = _HLO_IOTA_RE.search(line)
    if m:
        import numpy as np

        g, k = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims)))
        if g * k == ids.size:
            if m.group(4):
                perm = [int(x) for x in m.group(4).split(",")]
                ids = ids.reshape(dims).transpose(perm).reshape(-1)
            return [tuple(int(x) for x in row)
                    for row in ids.reshape(g, k)]
    if "replica_groups={}" in line and nworld:
        # the empty-group spelling means "all devices, one group"
        return [tuple(range(nworld))]
    return None


def _axis_groups(mesh) -> Dict[Tuple[str, ...], frozenset]:
    """For each non-empty axis combination of ``mesh``: the partition
    of LOGICAL device ids (positions in ``mesh.devices.flat`` — the
    executable's partition-id order) into groups that vary over those
    axes with the others held fixed."""
    import itertools

    import numpy as np

    names = tuple(mesh.axis_names)
    shape = tuple(int(mesh.shape[n]) for n in names)
    ids = np.arange(int(np.prod(shape))).reshape(shape)
    out: Dict[Tuple[str, ...], frozenset] = {}
    for r in range(1, len(names) + 1):
        for combo in itertools.combinations(range(len(names)), r):
            moved = np.moveaxis(ids, combo, range(len(shape) - r,
                                                  len(shape)))
            flat = moved.reshape(-1, int(np.prod(
                [shape[i] for i in combo])))
            out[tuple(names[i] for i in combo)] = frozenset(
                frozenset(int(x) for x in row) for row in flat)
    return out


def hlo_collectives(compiled, mesh=None) -> List[dict]:
    """Collective ledger off a COMPILED executable's post-optimization
    HLO — the layer that sees what GSPMD inserted.  Same entry layout
    as :func:`jaxpr_collectives`; ``axes`` is recovered by matching
    each op's ``replica_groups`` against the partitions every axis
    combination of ``mesh`` induces (None when no mesh was given, the
    groups match no axis combination, or the op carries no groups —
    ``collective-permute`` uses ``source_target_pairs``; the jaxpr
    layer attributes those).  Async pairs count at the ``-start`` op;
    ``-done`` ops are skipped.

    Counting caveat: this layer counts op SITES in the optimized
    program text — a collective inside an HLO ``while`` body counts
    once, however many iterations run.  For GSPMD variants (no loops)
    sites equal per-step executions; for scanned schedules (pipeline)
    the jaxpr layer's trip-count-multiplied numbers are the per-step
    truth."""
    text = compiled.as_text()
    if not isinstance(text, str):  # some builds return a list of modules
        text = "\n".join(str(t) for t in text)
    by_axes = _axis_groups(mesh) if mesh is not None else {}
    nworld = int(mesh.devices.size) if mesh is not None else 0
    acc: Dict[_Key, dict] = {}
    for line in text.splitlines():
        m = _HLO_OP_RE.search(line)
        if m is None or m.group("suffix") == "-done":
            continue
        kind = HLO_COLLECTIVES[m.group("op")]
        per_call = _type_bytes(m.group("type"))
        axes: Optional[Tuple[str, ...]] = None
        groups = _parse_groups(line, nworld)
        if groups is not None and by_axes:
            gset = frozenset(frozenset(g) for g in groups)
            for combo, expected in by_axes.items():
                if gset == expected:
                    axes = combo
                    break
        key = (kind, axes)
        cell = acc.setdefault(
            key, {"count": 0, "bytes": 0, "bytes_per_call": 0})
        cell["count"] += 1
        cell["bytes"] += per_call
        cell["bytes_per_call"] = max(cell["bytes_per_call"], per_call)
    return _entries(acc)


# -- rollups ---------------------------------------------------------------

def collective_signature(entries: Sequence[dict]) -> Dict[str, int]:
    """``{kind: total count}`` — the shape tests pin ("zero1 =
    reduce_scatter + all_gather where dp = all_reduce only")."""
    out: Dict[str, int] = {}
    for e in entries:
        out[e["kind"]] = out.get(e["kind"], 0) + int(e["count"])
    return out


def merge_entries(*entry_lists: Sequence[dict]) -> List[dict]:
    """Sum several ledgers (e.g. a serve engine's program pool) into
    one, keyed on (kind, axes)."""
    acc: Dict[_Key, dict] = {}
    for entries in entry_lists:
        for e in entries:
            key = (e["kind"], tuple(e["axes"]) if e.get("axes") else None)
            cell = acc.setdefault(
                key, {"count": 0, "bytes": 0, "bytes_per_call": 0})
            cell["count"] += int(e["count"])
            cell["bytes"] += int(e["bytes"])
            cell["bytes_per_call"] = max(cell["bytes_per_call"],
                                         int(e.get("bytes_per_call", 0)))
    return _entries(acc)


def total_bytes(entries: Sequence[dict]) -> int:
    """Σ buffer bytes a step moves through collectives."""
    return sum(int(e["bytes"]) for e in entries)
