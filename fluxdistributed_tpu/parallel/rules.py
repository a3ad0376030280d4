"""Declarative sharding rules: a ~10-line regex-on-path rule table turns
into a full PartitionSpec tree for ANY model.

This module is the one place that decides which leaf is split over
which mesh axis; :mod:`.layout` supplies the dp x fsdp x tp grid the
axes live on, and ``prepare_training(layout=...)`` compiles the
unchanged dp step with the derived shardings.  Placement is DATA:

* :func:`match_partition_rules` — EasyLM-style (SNIPPETS.md [3]): walk
  the param tree, '/'-join each leaf path, take the FIRST rule whose
  regex ``re.search``-matches, and use its value as the leaf's
  PartitionSpec.  Scalars and single-element leaves always replicate.
* :class:`ShardLargest` — a shape-driven rule value (the paranum-style
  size threshold, SNIPPETS.md [2], generalized by
  :func:`fsdp_leaf_spec`): shard the leaf's largest still-unsharded
  divisible dim over one mesh axis.  This is how ZeRO-style
  parameter/optimizer sharding (arXiv:2004.13336 extended to ZeRO-3
  placement) becomes ONE rule —
  ``(".*", ShardLargest(mesh.FSDP_AXIS))`` — instead of a per-model
  walk, and how it composes with tensor-parallel rules: a
  :func:`with_fsdp` overlay applies it on top of an existing spec
  tree's leftover dims (the 2-D/3-D recipe).
* **Fallback**: an unmatched leaf replicates (``P()``).  That is the
  safe default but also the silent memory trap — a 4 GB embedding
  falling to replication fits nowhere — so every resolution also
  produces a :class:`RuleReport` naming dead rules and large unmatched
  leaves (``strict=True`` raises on the latter; fdtpu-lint's FDT108
  checks the committed tables against registered probe models).
* **Validation**: :func:`validate_rules` rejects axis names not
  declared on the mesh, and :func:`validate_specs` runs the spec tree
  through ``analysis.jaxpr_checks.check_spec_tree`` (axis exists +
  divisibility) against real leaf shapes BEFORE any memory is
  committed.

The committed tables (:data:`RULE_TABLES`) are the Megatron recipes for
the transformer LM and the ViT, the one-rule ZeRO-3 table and the empty
dp table; tests/test_rules.py pins the spec tree each gives, leaf for
leaf, so a table edit that moves a leaf is seen.  A new model family
gets a table here and one entry in :func:`rules_for_model`.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, PartitionSpec as P

from .. import mesh as mesh_lib
from .dp import TrainState

Pytree = Any

__all__ = [
    "ShardLargest",
    "Rule",
    "RuleReport",
    "RuleTable",
    "RULE_TABLES",
    "FALLBACK_MIN_SIZE",
    "fsdp_leaf_spec",
    "match_partition_rules",
    "with_fsdp",
    "rule_report",
    "validate_rules",
    "validate_specs",
    "broadcast_prefix",
    "train_state_specs",
    "dp_rules",
    "fsdp_rules",
    "lm_tp_rules_table",
    "vit_tp_rules_table",
    "rules_for_model",
    "registered_rule_tables",
]

#: leaves smaller than this stay replicated: sharding a 64-float
#: BatchNorm bias saves nothing and costs a latency-bound collective
#: per use.  The same scale decides the report: an UNMATCHED leaf at
#: or above it falling to replication is reported (and rejected under
#: ``strict=True``) — below it, replication is the right answer, not
#: a trap
FALLBACK_MIN_SIZE = 2 ** 11


def fsdp_leaf_spec(
    shape, axis: str = mesh_lib.DATA_AXIS, nshards: int = 1,
    min_size: int = FALLBACK_MIN_SIZE, base: P | None = None,
) -> P:
    """PartitionSpec for one leaf, chosen from its shape alone.

    Shards the largest dimension divisible by ``nshards`` (ties broken
    toward the trailing dim — for conv HWIO / dense (in, out) kernels
    that is the output-features dim, giving contiguous lanes-friendly
    shards).  Leaves with fewer than ``min_size`` elements, or no
    divisible dim, stay replicated.

    ``base`` composes with an existing spec (the fsdp x tp layouts):
    only dims the base leaves unsharded are candidates, and the base's
    entries are preserved in the result.

    The rule is a pure function of shape (and base), so a parameter and
    its optimizer-state slots (momentum/Adam moments have the param's
    shape) always agree — the property that lets one spec tree cover the
    whole ``TrainState``.
    """
    entries = (
        list(base) + [None] * (len(shape) - len(base))
        if base is not None
        else [None] * len(shape)
    )
    keep = P(*entries) if base is not None else P()
    if not shape or int(np.prod(shape)) < min_size:
        return keep
    best = None  # (extent, dim)
    for d, extent in enumerate(shape):
        if entries[d] is None and extent % nshards == 0 and extent >= nshards:
            if best is None or extent >= best[0]:
                best = (extent, d)
    if best is None:
        return keep
    entries[best[1]] = axis
    return P(*entries)


@dataclasses.dataclass(frozen=True)
class ShardLargest:
    """Shape-driven rule value: shard the leaf's largest
    still-unsharded dim divisible by the axis size over ``axis``
    (:func:`fsdp_leaf_spec` semantics — ties break toward the
    trailing dim; leaves under ``min_size`` elements, or with no
    divisible dim, keep their base spec).  Resolution needs a mesh
    (the axis size), which :func:`match_partition_rules` provides."""

    axis: str = mesh_lib.FSDP_AXIS
    min_size: int = FALLBACK_MIN_SIZE


#: one rule: (regex searched against the '/'-joined leaf path, value).
#: The value is a PartitionSpec or a ShardLargest.
Rule = Tuple[str, Any]


@dataclasses.dataclass
class RuleReport:
    """What a rule resolution actually did — the honesty record behind
    the replication fallback (and FDT108's input)."""

    #: rule pattern → leaf paths it decided
    matched: dict
    #: rule patterns that decided NO leaf
    dead: list
    #: (path, elements) for every unmatched non-scalar leaf (fell to
    #: replication)
    unmatched: list
    #: the subset of ``unmatched`` at/above the size threshold — the
    #: silent-replication trap FDT108 flags
    large_unmatched: list


def _is_spec(x) -> bool:
    return isinstance(x, P)


def _leaf_path(kp) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
        for k in kp)


def _resolve_value(value, shape, mesh: Optional[Mesh], base: P = None):
    if isinstance(value, ShardLargest):
        if mesh is None:
            raise ValueError(
                "a ShardLargest rule value needs a mesh to resolve "
                "(its axis size drives divisibility) — pass mesh= to "
                "match_partition_rules")
        if value.axis not in mesh.shape:
            raise ValueError(
                f"ShardLargest axis {value.axis!r} is not on the mesh "
                f"(axes: {sorted(dict(mesh.shape))})")
        return fsdp_leaf_spec(
            shape, value.axis, int(mesh.shape[value.axis]),
            min_size=value.min_size, base=base)
    if value is None:
        return P()
    if isinstance(value, P):
        return value
    raise TypeError(
        f"rule value {value!r} is neither a PartitionSpec nor a "
        "ShardLargest")


def match_partition_rules(
    rules: Sequence[Rule],
    params: Pytree,
    *,
    mesh: Optional[Mesh] = None,
    min_size: int = FALLBACK_MIN_SIZE,
    strict: bool = False,
    report: Optional[RuleReport] = None,
) -> Pytree:
    """PartitionSpec tree for ``params`` from a regex rule table.

    First match wins (order the specific patterns before the broad
    ones); scalars/single-element leaves replicate unconditionally;
    unmatched leaves fall to replication, recorded in ``report`` (pass
    a fresh :class:`RuleReport` to collect it; ``strict=True``
    additionally raises when an unmatched leaf has >= ``min_size``
    elements — the silent-replication trap).  ``mesh`` is required
    when any rule value is a :class:`ShardLargest` and is also used to
    pre-validate axis names via :func:`validate_rules`.
    """
    if mesh is not None:
        validate_rules(rules, mesh)
    compiled = [(re.compile(pat), pat, val) for pat, val in rules]
    rep = report if report is not None else RuleReport({}, [], [], [])
    for _, pat, _ in compiled:
        rep.matched.setdefault(pat, [])

    def decide(kp, leaf):
        path = _leaf_path(kp)
        shape = np.shape(leaf)
        if len(shape) == 0 or int(np.prod(shape)) <= 1:
            return P()
        for rx, pat, val in compiled:
            if rx.search(path) is not None:
                rep.matched[pat].append(path)
                return _resolve_value(val, shape, mesh)
        n = int(np.prod(shape))
        rep.unmatched.append((path, n))
        if n >= min_size:
            rep.large_unmatched.append((path, n))
        return P()

    specs = jax.tree_util.tree_map_with_path(decide, params)
    rep.dead = [pat for _, pat, _ in compiled if not rep.matched[pat]]
    if strict and rep.large_unmatched:
        worst = ", ".join(
            f"{p} ({n} elems)" for p, n in rep.large_unmatched[:5])
        raise ValueError(
            f"{len(rep.large_unmatched)} unmatched leaf(ves) of >= "
            f"{min_size} elements fell to replication: {worst} — add a "
            "rule (or a ShardLargest catch-all), or drop strict=True "
            "if replication is intended")
    return specs


def with_fsdp(
    specs: Pytree,
    params: Pytree,
    mesh: Mesh,
    axis: str = mesh_lib.FSDP_AXIS,
    min_size: int = FALLBACK_MIN_SIZE,
) -> Pytree:
    """Overlay ZeRO-style fully-sharded placement on an existing spec
    tree: every large leaf's largest still-unsharded dim is sharded
    over ``axis`` (existing entries — e.g. tensor-parallel dims — are
    preserved).  ``rules → with_fsdp`` is the 2-D/3-D composition: the
    standard large-model recipe of tensor parallelism per the table
    PLUS ZeRO sharding of what the table left whole, so per-device
    param/opt memory is about size / (|fsdp| x |model|)."""
    n = int(mesh.shape[axis])
    return jax.tree_util.tree_map(
        lambda spec, leaf: fsdp_leaf_spec(
            np.shape(leaf), axis, n, min_size=min_size, base=spec),
        specs, params, is_leaf=_is_spec)


def rule_report(rules: Sequence[Rule], params: Pytree,
                min_size: int = FALLBACK_MIN_SIZE) -> RuleReport:
    """Resolve ``rules`` against ``params`` purely for the report —
    dead rules + unmatched leaves (FDT108's engine).  Shape-driven
    values resolve as replicated here (no mesh): only MATCHING is
    reported, not the final placement."""
    rep = RuleReport({}, [], [], [])
    safe = [(pat, P() if isinstance(val, ShardLargest) else val)
            for pat, val in rules]
    match_partition_rules(
        safe, params, min_size=min_size, report=rep)
    return rep


def _spec_axes(spec) -> Tuple[str, ...]:
    out = []
    for entry in spec:
        if entry is None:
            continue
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            out.append(str(a))
    return tuple(out)


def validate_rules(rules: Sequence[Rule], mesh: Mesh) -> None:
    """Reject rule values naming axes the mesh does not declare —
    BEFORE tracing, with the offending rule named (GSPMD's own error
    comes at compile time and names neither)."""
    axes = set(dict(mesh.shape))
    for pat, val in rules:
        if isinstance(val, ShardLargest):
            bad = () if val.axis in axes else (val.axis,)
        elif val is None:
            bad = ()
        elif isinstance(val, P):
            bad = tuple(a for a in _spec_axes(val) if a not in axes)
        else:
            raise TypeError(
                f"rule {pat!r} value {val!r} is neither a PartitionSpec "
                "nor a ShardLargest")
        if bad:
            raise ValueError(
                f"rule {pat!r} names mesh axis(es) {sorted(set(bad))} "
                f"not on the mesh (axes: {sorted(axes)}) — source axis "
                "names from fluxdistributed_tpu.mesh constants and "
                "build the mesh with those axes")


def validate_specs(specs: Pytree, shapes: Pytree, mesh: Mesh,
                   where: str = "rules") -> None:
    """Run a derived spec tree through the lint suite's
    ``check_spec_tree`` (axis exists + sharded dims divisible) and
    raise ONE ValueError carrying every finding — the same validation
    a jaxpr-layer sweep would report, applied eagerly at layout-build
    time where the fix is one rule away.

    The two trees are aligned leaf-by-leaf HERE (flattening ``shapes``
    with arrays as leaves) because ``check_spec_tree``'s raw-tuple
    heuristic would otherwise mistake tuple-structured state — Adam's
    ``(m, v)`` pairs — for shape literals."""
    from jax.tree_util import keystr

    from ..analysis.jaxpr_checks import check_spec_tree

    is_spec = lambda x: x is None or isinstance(x, P)  # noqa: E731
    sflat = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_spec)[0]
    aflat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: x is None)[0]
    if len(sflat) != len(aflat):
        raise ValueError(
            f"{where}: spec tree has {len(sflat)} leaves but the state "
            f"tree has {len(aflat)} — regenerate the specs from the "
            "live state tree")
    specs_d, shapes_d = {}, {}
    for i, ((pth, spec), (_, leaf)) in enumerate(zip(sflat, aflat)):
        if leaf is None or spec is None:
            continue
        key = f"{i}{keystr(pth)}"
        specs_d[key] = spec
        shapes_d[key] = tuple(np.shape(leaf))
    findings = check_spec_tree(shapes_d, specs_d, mesh, where=where)
    if findings:
        msgs = "; ".join(f.message for f in findings[:8])
        raise ValueError(
            f"rule-derived specs failed validation ({len(findings)} "
            f"finding(s)): {msgs}")


def broadcast_prefix(specs: Pytree, tree: Pytree) -> Pytree:
    """Broadcast a prefix tree of PartitionSpecs over a deeper tree.

    Optimizer states mirror the param tree but may nest extra structure
    per param (Adam's ``(m, v)`` tuples); each param's spec is applied to
    every array in its state subtree.
    """
    treedef = jax.tree.structure(specs, is_leaf=_is_spec)
    subtrees = treedef.flatten_up_to(tree)
    leaves = jax.tree.leaves(specs, is_leaf=_is_spec)
    mapped = [jax.tree.map(lambda _, s=s: s, sub) for s, sub in zip(leaves, subtrees)]
    return jax.tree.unflatten(treedef, mapped)


def train_state_specs(state: TrainState, p_specs: Pytree) -> TrainState:
    """A ``TrainState`` of specs from a param spec tree: optimizer
    state broadcast from its param's spec (:func:`broadcast_prefix` —
    Adam moments share the param's shape, so the shape-driven and
    broadcast answers agree), mutable model state and the step counter
    replicated."""
    return TrainState(
        params=p_specs,
        opt_state=broadcast_prefix(p_specs, state.opt_state),
        model_state=jax.tree.map(lambda _: P(), state.model_state),
        step=P(),
    )


# -- committed rule tables ---------------------------------------------------
#
# Each table's spec tree is pinned leaf for leaf in tests/test_rules.py.
# Patterns are ordered specific-first: the matcher takes the FIRST hit
# ("qkv/kernel$" must win before a hypothetical broad "kernel$").


def dp_rules() -> list:
    """Plain data parallelism: no parameter sharding at all — the
    empty table (every leaf falls to replication, which IS the dp/
    zero1 placement; ZeRO-1's flat optimizer shards are an internal
    re-layout of the update, not a spec-tree property)."""
    return []


def fsdp_rules(axis: str = mesh_lib.FSDP_AXIS,
               min_size: int = FALLBACK_MIN_SIZE) -> list:
    """ZeRO-3 placement as ONE rule: every large leaf's largest
    divisible dim shards over ``axis``; small leaves (BatchNorm
    scales, biases) stay replicated."""
    return [(r".*", ShardLargest(axis, min_size=min_size))]


def lm_tp_rules_table(model_axis: str = mesh_lib.MODEL_AXIS,
                      shard_vocab: bool = True) -> list:
    """The Megatron recipe for ``models.transformer_lm.TransformerLM``:
    qkv (or, with ``num_kv_heads`` set, the separate q and kv
    projections) column-sharded over heads, attention out row-sharded,
    MLP up (gelu Dense_0 / swiglu gate+up) column- and down
    (Dense_1/down) row-sharded, so each block needs exactly two
    all-reduces.  ``embed/embedding [vocab, dim]`` is vocab-sharded
    (Megatron's parallel vocab embedding — with tied embeddings the
    logits come out vocab-sharded and GSPMD all-gathers at the f32
    log-softmax).  Requires heads, kv heads, mlp_dim and (if
    ``shard_vocab``) vocab divisible by the model-axis size.
    "qkv/" is listed before "kv/" and "q/": the first hit decides."""
    rules = []
    if shard_vocab:
        rules.append((r"embed/embedding$", P(model_axis, None)))
    rules += [
        (r"qkv/kernel$", P(None, None, model_axis, None)),
        (r"qkv/bias$", P(None, model_axis, None)),
        (r"kv/kernel$", P(None, None, model_axis, None)),
        (r"kv/bias$", P(None, model_axis, None)),
        (r"q/kernel$", P(None, model_axis, None)),
        (r"q/bias$", P(model_axis, None)),
        (r"out/kernel$", P(model_axis, None, None)),
        (r"head/kernel$", P(None, model_axis)),
        (r"head/bias$", P(model_axis)),
        (r"Dense_0/kernel$", P(None, model_axis)),
        (r"Dense_0/bias$", P(model_axis)),
        (r"Dense_1/kernel$", P(model_axis, None)),
        (r"(gate|up)/kernel$", P(None, model_axis)),
        (r"down/kernel$", P(model_axis, None)),
    ]
    return rules


def vit_tp_rules_table(model_axis: str = mesh_lib.MODEL_AXIS) -> list:
    """The Megatron recipe for ``models.vit.ViT`` encoder blocks: qkv
    kernel ``[dim, 3, heads, head_dim]`` and out kernel ``[heads,
    head_dim, dim]`` sharded over heads, MlpBlock Dense_0 column- and
    Dense_1 row-sharded; patch embed / norms / head and the biases of
    row-sharded layers replicate via the fallback."""
    return [
        (r"qkv/kernel$", P(None, None, model_axis, None)),
        (r"qkv/bias$", P(None, model_axis, None)),
        (r"out/kernel$", P(model_axis, None, None)),
        (r"MlpBlock.*Dense_0/kernel$", P(None, model_axis)),
        (r"MlpBlock.*Dense_0/bias$", P(model_axis)),
        (r"MlpBlock.*Dense_1/kernel$", P(model_axis, None)),
    ]


@dataclasses.dataclass(frozen=True)
class RuleTable:
    """A committed, named rule table plus the probe models FDT108
    checks it against (each probe: ``() -> (params_shapes, note)``
    where ``params_shapes`` is an eval_shape'd param tree — building a
    probe allocates nothing)."""

    name: str
    build: Callable[[], list]
    probes: Tuple[Callable[[], Tuple[Any, str]], ...]
    #: tables that intentionally match nothing (dp) or catch-all
    #: (fsdp) skip the large-unmatched check — replication/sharding of
    #: every leaf is their DOCUMENTED semantics, not a silent fallback
    check_unmatched: bool = True


def _probe_params(model, sample_shape, dtype="float32"):
    """eval_shape the model's init — param SHAPES without allocating
    a single buffer (rule matching and FDT108 only need paths and
    shapes)."""
    import jax.numpy as jnp

    sample = jax.ShapeDtypeStruct(sample_shape, jnp.dtype(dtype))
    variables = jax.eval_shape(
        lambda s: model.init(jax.random.PRNGKey(0), s, train=False),
        sample)
    return variables["params"]


def _lm_probe(gqa: bool = False, swiglu: bool = False,
              tied: bool = True):
    def build():
        from ..models.transformer_lm import TransformerLM

        model = TransformerLM(
            vocab=32, dim=16, depth=2, num_heads=4, mlp_dim=32,
            num_kv_heads=2 if gqa else None,
            mlp="swiglu" if swiglu else "gelu",
            tie_embeddings=tied)
        note = (f"TransformerLM(gqa={gqa}, swiglu={swiglu}, "
                f"tied={tied})")
        return _probe_params(model, (1, 8), "int32"), note

    return build


def _vit_probe():
    from ..models.vit import ViT

    model = ViT(patch=4, depth=2, dim=16, num_heads=4, mlp_dim=32,
                num_classes=4)
    return _probe_params(model, (1, 8, 8, 3)), "ViT(tiny)"


def _cnn_probe():
    from ..models.simple import SimpleCNN

    model = SimpleCNN(num_classes=4, features=8)
    return _probe_params(model, (1, 8, 8, 3)), "SimpleCNN(tiny)"


#: name → committed table.  FDT108 sweeps every entry: a pattern that
#: matches NO leaf on any probe is a dead rule; a probe leaf >=
#: FALLBACK_MIN_SIZE matched by nothing is a silent replication.
RULE_TABLES = {
    "dp": RuleTable(
        "dp", dp_rules,
        probes=(_lm_probe(), _vit_probe, _cnn_probe),
        check_unmatched=False),
    "fsdp": RuleTable(
        "fsdp", fsdp_rules,
        probes=(_lm_probe(), _vit_probe, _cnn_probe),
        check_unmatched=False),
    "lm_tp": RuleTable(
        "lm_tp", lm_tp_rules_table,
        probes=(_lm_probe(), _lm_probe(gqa=True),
                _lm_probe(swiglu=True), _lm_probe(tied=False))),
    "vit_tp": RuleTable(
        "vit_tp", vit_tp_rules_table, probes=(_vit_probe,)),
}


def registered_rule_tables() -> dict:
    return dict(RULE_TABLES)


def rules_for_model(model, tp: bool = True) -> list:
    """The committed table for a model family: transformer LM / ViT
    get their Megatron tables (``tp=False`` — a layout with no model
    axis — drops to the empty table so the fsdp overlay alone decides
    placement); everything else (conv stacks, torch imports of them)
    uses the empty table + overlay, which is exactly what makes a new
    model shardable with NO spec code."""
    from ..models.transformer_lm import TransformerLM
    from ..models.vit import ViT

    if not tp:
        return dp_rules()
    if isinstance(model, TransformerLM):
        return lm_tp_rules_table()
    if isinstance(model, ViT):
        return vit_tp_rules_table()
    return dp_rules()
