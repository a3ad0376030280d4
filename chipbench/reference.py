"""Drive a configuration's plain reference through the first steps of a
run: the same seeded weights, the same rows, its own loss, gradients and
optimizer.  Imports nothing of the program.

``rows_used`` plants the faults a training cell can have in the
reference put in the program's place: 0.5 leaves half of the batch out
and takes the mean over the rest; ``1 / chips`` is what one chip holds
when the exchange between chips is left out.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import refcommon


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def delta_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def _named(tree, values) -> dict:
    return dict(zip(leaf_names(tree), (float(v) for v in values)))


def first_steps(cfg, ref, key, batches, mode: str = "f32",
                rows_used: float = 1.0) -> dict:
    """``batches``: [(images, integer labels)] as numpy, one per step.
    Returns the losses, the first gradient's norm per leaf, and per leaf
    the norm of the change of parameters and of model state over the
    steps."""
    prec = refcommon.Precision(mode)
    name = cfg["optimizer"]["factory"]
    hp = cfg["optimizer"]["kwargs"]
    opt_init, opt_step, _ = refcommon.OPTIMIZERS[name]
    make = jax.jit(functools.partial(ref.make_params, cfg))
    params, mstate = make(key)
    p0, s0 = make(key)
    opt_state = jax.jit(opt_init)(params)

    @jax.jit
    def block_grad(params, mstate, images, labels):
        def lossf(p):
            logits, new = ref.forward(cfg, prec, p, mstate, images)
            return refcommon.cross_entropy_sum(logits, labels), new
        (loss, new), grads = jax.value_and_grad(lossf, has_aux=True)(params)
        return loss, grads, new

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda a, s: jax.tree.map(lambda x: x * s, a),
                    donate_argnums=0)
    apply = jax.jit(functools.partial(opt_step, hp), donate_argnums=(0, 2))

    losses, grad1 = [], None
    for step, (images, labels) in enumerate(batches):
        n = max(1, math.ceil(len(images) * rows_used))
        images, labels = images[:n], labels[:n]
        block = ref.ROW_BLOCK or n
        loss, grads, new = 0.0, None, mstate
        for lo in range(0, n, block):
            l, g, new = block_grad(params, mstate, jnp.asarray(images[lo:lo + block]),
                                   jnp.asarray(labels[lo:lo + block]))
            loss = loss + l
            grads = g if grads is None else add(grads, g)
        grads = scale(grads, 1.0 / n)
        losses.append(float(loss) / n)
        if step == 0:
            grad1 = _named(grads, leaf_norms(grads))
        params, opt_state = apply(params, grads, opt_state, step)
        mstate = new
    return {
        "losses": losses,
        "grad1": grad1,
        "delta": _named(params, delta_norms(params, p0)),
        "state_delta": _named(mstate, delta_norms(mstate, s0)),
    }


# -- the comparison ----------------------------------------------------------

def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    names = [n for n in ref if keep is None or n in keep]
    if not names:
        return {}
    floor = float(np.median([ref[n] for n in names]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
            for n in names}


def worst_leaf(prog: dict, ref: dict, keep=None):
    """``(gap, leaf name)`` of the worst leaf; ``(0.0, None)`` where no
    leaf counts."""
    gaps = leaf_gaps(prog, ref, keep)
    if not gaps:
        return 0.0, None
    return max((g, n) for n, g in gaps.items())


def worst_gap(prog: dict, ref: dict, keep=None) -> float:
    return worst_leaf(prog, ref, keep)[0]


def median_gap(prog: dict, ref: dict, keep=None) -> float:
    gaps = leaf_gaps(prog, ref, keep)
    return float(np.median(list(gaps.values()))) if gaps else 0.0


def live_leaves(ref_grad1: dict) -> set:
    """Leaves whose first gradient in the reference is not nought to
    rounding: a thousandth of the median leaf's or more."""
    floor = 1e-3 * float(np.median(list(ref_grad1.values())))
    return {n for n, v in ref_grad1.items() if v >= floor}


def head_leaves(ref_mod, names) -> set:
    """The leaves of the layer the loss reads: their first gradient is a
    function of the forward pass alone."""
    return {n for n in names
            if any(n.startswith(f"['{h}']") for h in ref_mod.HEAD)}


def compare(prog: dict, ref: dict, ref_mod) -> dict:
    """Every number a training cell can be held to, by short plain names.
    The cell's file of limits says which of them are compared.

    ``*_worst`` is the worst leaf's gap, as the builder's contract has
    it; where a few small leaves swing whatever the precision (PERF.md,
    "Comparison"), the cell compares the median leaf's gap (no suffix),
    which is steady from seed to seed.  ``head_gap`` is the worst gap of
    the first gradient among the leaves of the layer the loss reads: a
    function of the forward pass alone."""
    live = live_leaves(ref["grad1"])
    head = head_leaves(ref_mod, ref["grad1"])
    out = {
        "loss1_gap": abs(prog["losses"][0] - ref["losses"][0]),
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"])),
        "grad_gap": median_gap(prog["grad1"], ref["grad1"]),
        "grad_worst": worst_gap(prog["grad1"], ref["grad1"]),
        "head_gap": worst_gap(prog["grad1"], ref["grad1"], keep=head),
        "update_gap": median_gap(prog["delta"], ref["delta"], keep=live),
        "update_worst": worst_gap(prog["delta"], ref["delta"], keep=live),
    }
    if ref["state_delta"]:
        out["stats_gap"] = median_gap(prog["state_delta"], ref["state_delta"])
        out["stats_worst"] = worst_gap(prog["state_delta"], ref["state_delta"])
    return out


def worst_leaves(prog: dict, ref: dict) -> dict:
    """Which leaf each ``*_worst`` number is, for the notes."""
    out = {"grad": worst_leaf(prog["grad1"], ref["grad1"])[1],
           "update": worst_leaf(prog["delta"], ref["delta"],
                                live_leaves(ref["grad1"]))[1]}
    if ref["state_delta"]:
        out["stats"] = worst_leaf(prog["state_delta"], ref["state_delta"])[1]
    return out


def judge(numbers: dict, limits: dict):
    """``(correct, {name: {"value", "limit"}})`` over the numbers that
    have a limit.  A limit without its number, or a number that is not
    finite or is over its limit, fails; no limit at all fails too."""
    table, ok = {}, bool(limits)
    for name in sorted(limits):
        v, lim = numbers.get(name), limits[name]
        table[name] = {"value": v, "limit": lim}
        if v is None or not math.isfinite(v) or v > lim:
            ok = False
    return ok, table
