"""dp×fsdp×tp layouts and the auto-layout picker — "fit this model on
this topology" as one flag instead of per-model spec code.

A :class:`Layout` is just three axis sizes on the
``mesh.make_mesh_3d`` mesh ``(data, fsdp, model)``:

* ``dp`` — replicas (batch shards, parameters replicated),
* ``fsdp`` — ZeRO-style parameter/optimizer sharding (the batch ALSO
  shards over it, jointly with ``data``),
* ``tp`` — the Megatron model axis (rule tables decide which dims).

The parameter placement comes from the declarative rules engine
(:mod:`.rules`): the model family's committed table decides the
tensor-parallel dims, :func:`.rules.with_fsdp` overlays the ZeRO
sharding on every large leaf's leftover dim, and the derived spec tree
drives the UNCHANGED dp train step (``dp.make_train_step`` with
``state_shardings`` + a ``("data", "fsdp")`` batch) — GSPMD composes
the collectives from the annotations (arXiv:1810.09868's full-program
partitioning): a per-layer all-gather and a gradient reduce-scatter
over ``fsdp`` (the ZeRO-3 schedule), two all-reduces a block over
``model``.

:func:`pick` is the auto-layout picker ROADMAP item 3 promised: it
prices every candidate layout by compiling the REAL train step
abstractly (eval_shape'd state — no buffer is ever allocated), ranks
the candidates by per-device HBM headroom through the same
``rank_memory`` ranking ``bin/fit.py`` uses, and breaks ties among
fitting layouts by the compiled-HLO collective ledger
(:mod:`..obs.comms` — fewest bytes moved per step wins; plain dp
all-reduces grads once and beats fsdp's per-layer all-gathers whenever
it fits, which is exactly the intuition, now measured).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np

from .. import mesh as mesh_lib

__all__ = [
    "Layout",
    "LayoutError",
    "PickReport",
    "LAYOUT_PRESETS",
    "resolve_layout",
    "layout_candidates",
    "state_specs_for",
    "shard_state",
    "price_layouts",
    "pick",
]


class LayoutError(ValueError):
    """A layout cannot be built/priced/picked on this topology."""


@dataclasses.dataclass(frozen=True)
class Layout:
    """One point on the dp×fsdp×tp grid.  ``dp * fsdp * tp`` must
    equal the device count the mesh is built over."""

    name: str
    dp: int = 1
    fsdp: int = 1
    tp: int = 1

    @property
    def sizes(self) -> dict:
        return {mesh_lib.DATA_AXIS: self.dp, mesh_lib.FSDP_AXIS: self.fsdp,
                mesh_lib.MODEL_AXIS: self.tp}

    @property
    def batch_axes(self) -> Tuple[str, str]:
        """The batch dim shards over data AND fsdp jointly (size-1
        axes are harmless in a PartitionSpec entry)."""
        return (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS)

    @property
    def batch_shards(self) -> int:
        return self.dp * self.fsdp

    def devices(self) -> int:
        return self.dp * self.fsdp * self.tp

    def build_mesh(self, devs: Sequence | None = None):
        return mesh_lib.make_mesh_3d(self.dp, self.fsdp, self.tp,
                                     devs=devs)

    def validate_mesh(self, mesh) -> None:
        """A caller-supplied mesh must carry exactly this layout's
        axis sizes — a mismatch means the compiled specs and the
        physical mesh disagree."""
        got = {k: int(v) for k, v in dict(mesh.shape).items()}
        if got != self.sizes:
            raise LayoutError(
                f"mesh axes {got} do not match layout {self.name!r} "
                f"{self.sizes} — build the mesh with "
                "layout.build_mesh() or mesh.make_mesh_3d")

    def describe(self) -> str:
        return (f"{self.name}: dp={self.dp} x fsdp={self.fsdp} x "
                f"tp={self.tp}")


def _even_split(n: int) -> int:
    """The smallest non-trivial factor of ``n`` (2 for even counts) —
    the conservative dp extent the mixed presets use."""
    for k in (2, 3, 5, 7):
        if n % k == 0:
            return k
    return 1


#: preset name → (ndev -> Layout | None).  None = the preset does not
#: exist at this device count (e.g. dp_fsdp on 1 device).
LAYOUT_PRESETS: dict = {
    "dp": lambda n: Layout("dp", dp=n),
    "fsdp": lambda n: Layout("fsdp", fsdp=n) if n > 1 else None,
    "tp": lambda n: Layout("tp", tp=n) if n > 1 else None,
    "dp_fsdp": lambda n: (
        Layout("dp_fsdp", dp=_even_split(n), fsdp=n // _even_split(n))
        if n >= 4 and _even_split(n) > 1 else None),
    "fsdp_tp": lambda n: (
        Layout("fsdp_tp", fsdp=n // _even_split(n), tp=_even_split(n))
        if n >= 4 and _even_split(n) > 1 else None),
    "dp_fsdp_tp": lambda n: (
        Layout("dp_fsdp_tp", dp=2, fsdp=n // 4, tp=2)
        if n >= 8 and n % 4 == 0 else None),
}


def resolve_layout(spec, ndev: Optional[int] = None) -> Layout:
    """A Layout from a Layout (validated) or a preset name.  ``ndev``
    defaults to the process's device count."""
    import jax

    n = ndev if ndev is not None else jax.device_count()
    if isinstance(spec, Layout):
        if spec.devices() != n:
            raise LayoutError(
                f"layout {spec.describe()} covers {spec.devices()} "
                f"devices but the topology has {n}")
        return spec
    if isinstance(spec, str):
        fn = LAYOUT_PRESETS.get(spec)
        if fn is None:
            raise LayoutError(
                f"unknown layout preset {spec!r} "
                f"(known: {sorted(LAYOUT_PRESETS)}, or pass a Layout)")
        lay = fn(n)
        if lay is None:
            raise LayoutError(
                f"layout preset {spec!r} does not exist on {n} "
                "device(s)")
        return lay
    raise TypeError(f"layout must be a Layout or preset name, got "
                    f"{type(spec).__name__}")


def layout_candidates(ndev: Optional[int] = None) -> list:
    """Every preset that exists at this device count — the picker's
    default candidate set."""
    import jax

    n = ndev if ndev is not None else jax.device_count()
    out = []
    for name in LAYOUT_PRESETS:
        lay = LAYOUT_PRESETS[name](n)
        if lay is not None:
            out.append(lay)
    return out


def state_specs_for(model, state, layout: Layout, mesh,
                    min_size: Optional[int] = None):
    """The rule-derived ``TrainState`` spec tree for ``model`` under
    ``layout``: the model family's committed table decides the
    tensor-parallel dims (empty table when ``tp == 1``), the fsdp
    overlay shards every large leaf's leftover dim, optimizer state
    broadcasts from its param, and the whole tree is validated
    (axis names + divisibility) BEFORE any placement happens.  A
    ``tp > 1`` layout whose model family has no tensor-parallel table
    is rejected — a silently replicated model axis would burn devices.
    """
    from . import rules

    kw = {} if min_size is None else {"min_size": min_size}
    table = rules.rules_for_model(model, tp=layout.tp > 1)
    if layout.tp > 1 and not table:
        raise LayoutError(
            f"layout {layout.name!r} has a model axis (tp={layout.tp}) "
            f"but {type(model).__name__} has no tensor-parallel rule "
            "table — every leaf would replicate over it.  Use a dp/"
            "fsdp layout, or give the family a table in "
            "parallel/rules.py and an entry in rules.rules_for_model")
    p_specs = rules.match_partition_rules(
        table, state.params, mesh=mesh, **kw)
    if layout.fsdp > 1:
        p_specs = rules.with_fsdp(
            p_specs, state.params, mesh, axis=mesh_lib.FSDP_AXIS, **kw)
    spec_state = rules.train_state_specs(state, p_specs)
    rules.validate_specs(spec_state, state, mesh,
                         where=f"layout:{layout.name}")
    return spec_state


def shard_state(model, state, layout: Layout, mesh,
                min_size: Optional[int] = None):
    """Place ``state`` per :func:`state_specs_for`; returns the placed
    state and its ``NamedSharding`` tree (the ``state_shardings=`` of
    ``dp.make_train_step``).  Leaves are copied first
    (``sharding.unaliased``) so donating the placed state cannot delete
    the caller's source arrays."""
    import jax

    from ..sharding import make_shardings, unaliased

    sh = make_shardings(
        state_specs_for(model, state, layout, mesh, min_size=min_size), mesh)

    def put(x, s):
        return None if x is None else jax.device_put(unaliased(x), s)

    return jax.tree.map(put, state, sh, is_leaf=lambda x: x is None), sh


# -- the picker -------------------------------------------------------------


@dataclasses.dataclass
class PickReport:
    """What the picker decided and why — the artifact the driver
    prints and CI uploads next to the profile artifacts."""

    chosen: Optional[Layout]
    rows: list
    budget_bytes: Optional[float]
    reason: str

    def to_json(self) -> dict:
        return {
            "schema": "fdtpu-layout-pick/v1",
            "chosen": self.chosen.name if self.chosen else None,
            "chosen_sizes": self.chosen.sizes if self.chosen else None,
            "budget_bytes": self.budget_bytes,
            "reason": self.reason,
            "rows": self.rows,
        }

    def save(self, path: str) -> None:
        import os

        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)

    def describe(self) -> str:
        lines = []
        if self.budget_bytes is not None:
            lines.append(
                f"layout pick: per-device HBM budget "
                f"{self.budget_bytes:.3e} bytes")
        else:
            lines.append(
                "layout pick: NO HBM budget (backend reports no "
                "memory_stats and none was passed) — ranked by "
                "collective bytes only")
        for r in self.rows:
            peak = (f"peak {r['peak_bytes']:>13,}"
                    if r.get("peak_bytes") is not None
                    else "peak   unavailable")
            fits = {True: "FITS", False: "DOES NOT FIT",
                    None: "fit unknown"}[r.get("fits")]
            if r.get("comms_bytes") is not None:
                comms = f"collective bytes/step {r['comms_bytes']:,}"
            elif "invalid" in r:
                comms = f"invalid: {r['invalid']}"
            else:
                # priced fine, ledger extraction failed — a fitting
                # candidate must never read as "invalid"
                comms = ("collective ledger unavailable"
                         + (f" ({r['comms_unavailable']})"
                            if r.get("comms_unavailable") else ""))
            mark = " <== chosen" if (
                self.chosen and r["layout"] == self.chosen.name) else ""
            lines.append(
                f"  {r['layout']:<12} {peak}  {fits:<13} {comms}{mark}")
        lines.append(f"layout pick: {self.reason}")
        return "\n".join(lines)


def _loss_fn_for(model, loss_fn=None):
    from ..models.transformer_lm import TransformerLM, lm_loss_fn
    from ..ops import logitcrossentropy
    from .dp import flax_loss_fn

    if loss_fn is not None:
        return loss_fn
    if isinstance(model, TransformerLM):
        return lm_loss_fn(model)
    return flax_loss_fn(model, logitcrossentropy)


def _abstract_state(model, batch_struct, optimizer):
    """TrainState of ShapeDtypeStructs — the picker prices layouts
    without ever allocating a parameter buffer."""
    import jax

    from .dp import TrainState

    # the model_input convention (data/loader.py) without np coercion —
    # these are ShapeDtypeStructs, not arrays
    sample = None
    for k in ("image", "tokens"):
        if k in batch_struct:
            sample = batch_struct[k]
            break
    if sample is None:
        sample = next(iter(batch_struct.values()))

    def build(s):
        variables = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)}, s, train=True)
        params = variables["params"]
        mstate = {k: v for k, v in variables.items() if k != "params"}
        return TrainState.create(params, optimizer, model_state=mstate)

    return jax.eval_shape(build, sample)


def price_layouts(
    model,
    batch_struct: dict,
    optimizer=None,
    *,
    layouts: Optional[Sequence[Layout]] = None,
    loss_fn: Optional[Callable] = None,
    ndev: Optional[int] = None,
    min_size: Optional[int] = None,
) -> list:
    """Compile each candidate layout's REAL train step abstractly and
    return one row per candidate: ``peak_bytes`` off XLA's
    ``memory_analysis`` (None when this build lacks it), the compiled
    collective ledger rolled up per mesh axis, or ``invalid`` with the
    reason (indivisible batch, no TP table, indivisible heads, ...).

    ``batch_struct`` is a batch dict of arrays or ShapeDtypeStructs —
    shapes and dtypes are all that matters; nothing is executed."""
    import jax

    from ..obs import memstats
    from ..obs.comms import hlo_collectives, total_bytes
    from ..sharding import make_shardings
    from . import dp as dp_lib

    if optimizer is None:
        from .. import optim

        optimizer = optim.adam(1e-3)
    batch_struct = {
        k: jax.ShapeDtypeStruct(np.shape(v), getattr(v, "dtype", None))
        for k, v in batch_struct.items()}
    bsz = next(iter(batch_struct.values())).shape[0]
    lf = _loss_fn_for(model, loss_fn)
    cands = list(layouts) if layouts is not None else layout_candidates(ndev)
    state_struct = _abstract_state(model, batch_struct, optimizer)
    rows = []
    for lay in cands:
        row: dict = {"layout": lay.name, "sizes": lay.sizes,
                     "peak_bytes": None, "comms_bytes": None}
        if bsz % lay.batch_shards:
            row["invalid"] = (f"batch {bsz} not divisible by dp x fsdp "
                              f"= {lay.batch_shards}")
            rows.append(row)
            continue
        try:
            mesh = lay.build_mesh()
            spec_state = state_specs_for(
                model, state_struct, lay, mesh, min_size=min_size)
            sh = make_shardings(spec_state, mesh)
            step = dp_lib.make_train_step(
                lf, optimizer, mesh, axis=lay.batch_axes,
                donate=True, state_shardings=sh)
            compiled = step.lower(state_struct, batch_struct).compile()
        except (LayoutError, ValueError) as e:
            row["invalid"] = str(e)[:300]
            rows.append(row)
            continue
        mem = memstats.step_memory(step, (state_struct, batch_struct),
                                   compiled=compiled)
        if mem:
            row["peak_bytes"] = int(mem["peak_bytes"])
            row["memory"] = mem
        try:
            entries = hlo_collectives(compiled, mesh=mesh)
            row["comms"] = entries
            row["comms_bytes"] = int(total_bytes(entries))
            per_axis: dict = {}
            for e in entries:
                key = "+".join(e["axes"]) if e["axes"] else "?"
                per_axis[key] = per_axis.get(key, 0) + int(e["bytes"])
            row["comms_bytes_per_axis"] = per_axis
        except Exception as e:  # noqa: BLE001 — ledger is best-effort
            row["comms_unavailable"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)
    return rows


def pick(
    model,
    batch_struct: dict,
    optimizer=None,
    *,
    hbm_bytes: Optional[float] = None,
    layouts: Optional[Sequence[Layout]] = None,
    loss_fn: Optional[Callable] = None,
    ndev: Optional[int] = None,
    min_size: Optional[int] = None,
    rows: Optional[list] = None,
) -> PickReport:
    """Choose the fastest layout that fits this topology.

    The HBM headroom ranking rides the same ``rank_memory`` the fit
    checker (``bin/fit.py``) uses — ``hbm_bytes`` defaults to the live
    per-device ``bytes_limit`` and MUST be passed on backends without
    ``memory_stats()`` (CPU) for fit verdicts.  Among fitting layouts
    the per-step collective ledger breaks the tie: fewest buffer bytes
    moved wins (then most headroom).  With no budget at all the
    verdicts stay unknown and the ledger alone ranks — documented
    degradation, never a silent guess of "fits".

    Raises :class:`LayoutError` when a budget is known and NO
    candidate fits (the report rides the exception's ``report``
    attribute so callers can still print the ranking).

    ``rows`` short-circuits the pricing: pass a prior
    :func:`price_layouts` result to re-pick under a different budget
    without recompiling (rows are copied; the input list is never
    mutated).
    """
    import copy

    from ..obs import memstats

    if rows is None:
        rows = price_layouts(
            model, batch_struct, optimizer, layouts=layouts,
            loss_fn=loss_fn, ndev=ndev, min_size=min_size)
    else:
        rows = copy.deepcopy(list(rows))
    budget = hbm_bytes
    if budget is None:
        stats = memstats.hbm_device_stats()
        limits = [d["bytes_limit"] for d in (stats or [])
                  if d["bytes_limit"] > 0]
        if limits:
            budget = float(min(limits))
    # the fit checker's ranking over the same row shape it consumes
    ranked = memstats.rank_memory(
        {r["layout"]: {"memory": r.get("memory")} for r in rows
         if "invalid" not in r},
        budget)
    verdicts = {r["variant"]: r for r in ranked}
    for r in rows:
        v = verdicts.get(r["layout"])
        r["fits"] = v["fits"] if v else None
        r["headroom_bytes"] = v["headroom_bytes"] if v else None

    def _tiebreak(r):
        comms = r.get("comms_bytes")
        head = r.get("headroom_bytes")
        return (comms if comms is not None else float("inf"),
                -(head if head is not None else float("-inf")))

    valid = [r for r in rows if "invalid" not in r]
    fitting = [r for r in valid if r["fits"]]
    # "does not fit" is only a verdict when a peak was actually
    # measured: on builds without memory_analysis every row prices to
    # peak_bytes=None / fits=None, and the honest behavior is the same
    # ledger-only degradation as no-budget — never a false "exceeds
    # the budget" hard failure about peaks nobody measured
    any_peak = any(r.get("peak_bytes") is not None for r in valid)
    if fitting:
        best = min(fitting, key=_tiebreak)
        comms_txt = (f"{best['comms_bytes']:,} bytes/step"
                     if best.get("comms_bytes") is not None
                     else "ledger unavailable")
        reason = (f"chose {best['layout']} — fits with headroom "
                  f"{best['headroom_bytes']:,} bytes and the smallest "
                  f"collective traffic ({comms_txt}) among "
                  f"{len(fitting)} fitting layout(s)")
    elif budget is not None and valid and any_peak:
        report = PickReport(None, rows, budget,
                            "no candidate layout fits the budget")
        err = LayoutError(
            f"no layout fits: every candidate's peak exceeds the "
            f"per-device budget {budget:.3e} bytes "
            f"({[(r['layout'], r.get('peak_bytes')) for r in valid]})")
        err.report = report
        raise err
    elif valid:
        best = min(valid, key=_tiebreak)
        why = ("memory model unavailable on this build"
               if budget is not None and not any_peak
               else "no HBM budget — pass hbm_bytes for fit verdicts")
        reason = (f"chose {best['layout']} by collective traffic alone "
                  f"({why})")
    else:
        report = PickReport(None, rows, budget,
                            "no valid candidate layout")
        err = LayoutError(
            "no valid candidate layout on this topology: "
            + "; ".join(f"{r['layout']}: {r.get('invalid')}"
                        for r in rows))
        err.report = report
        raise err
    # resolve the winner from the ROW'S recorded axis sizes, never by
    # name alone: rows from a custom price_layouts(layouts=...) call
    # may share a preset's name with DIFFERENT sizes, and the caller
    # must train on exactly the mesh whose figures won the ranking
    sizes = best.get("sizes") or {}
    if sizes:
        chosen = Layout(best["layout"],
                        dp=int(sizes.get(mesh_lib.DATA_AXIS, 1)),
                        fsdp=int(sizes.get(mesh_lib.FSDP_AXIS, 1)),
                        tp=int(sizes.get(mesh_lib.MODEL_AXIS, 1)))
    else:
        chosen = next(l for l in (layouts or layout_candidates(ndev))
                      if l.name == best["layout"])
    return PickReport(chosen, rows, budget, reason)
