"""ZeRO-1 cross-replica weight-update sharding for the DP path.

Plain DP (``dp.make_train_step``) replicates the ``TrainState``: every
replica all-reduces the full gradient and then applies the IDENTICAL
full-model optimizer update — N devices burn memory and FLOPs on the
same Adam step (the redundancy the reference's per-device ``update``
loop has, src/ddp_tasks.jl:163-172).  "Automatic Cross-Replica Sharding
of Weight Update in Data-Parallel Training" (Xu et al., arXiv:2004.13336)
removes it without touching the model's parallelism:

1. **reduce-scatter** the gradients — each replica receives the SUM of
   one 1/N slice (half the wire bytes of the all-reduce it replaces),
2. apply the optimizer to that slice only — optimizer state lives
   sharded 1/N per device, update FLOPs drop N×,
3. **all-gather** the updated parameter slices back to replicated.

Numerics are identical to DP: the same summed gradient reaches the same
elementwise update, only *where* each element is updated changes.

Sharding is on the **flattened** leaf: each parameter/gradient leaf is
raveled to 1-D and zero-padded to a multiple of the data-axis size, so
ANY leaf shape shards evenly (contrast ``rules.fsdp_leaf_spec``, which
must hunt for a divisible dimension and leaves indivisible leaves
replicated).  Optimizer state mirrors that layout — flat padded leaves,
nested per-param exactly like the unsharded state (momentum/Adam slots
keep their tuple/dict structure), so the TP/PP state-spec machinery and
orbax checkpointing see a perfectly ordinary state tree whose leaves
happen to be 1-D and sharded.

Two implementations, mirroring ``dp.py``'s pair:

* ``make_train_step_zero1`` — pure GSPMD (default): the optimizer is
  wrapped by ``zero1_optimizer`` to flatten, constrain grads to
  ``P(data)`` (XLA turns the gradient all-reduce into the
  reduce-scatter), update, and constrain the result back to replicated
  (the all-gather) — the schedule is *derived* by the SPMD partitioner
  from annotations, exactly how ``fsdp.py`` gets ZeRO-3.  Composes
  unchanged with ``accum_steps``, ``steps_per_call`` (scan-K),
  ``donate``, and the trainer's OOM-skip because it IS
  ``dp.make_train_step`` with different shardings.
* ``make_train_step_zero1_shardmap`` — explicit collectives
  (``collectives.reduce_scatter`` / ``collectives.all_gather`` inside
  ``shard_map``), the literal schedule of the paper, for the
  explicit-SPMD story and as the base for manual-collective pipelines.
  Elementwise update rules only (each device updates a slice it cannot
  see past — LARS layer norms / global-norm clipping need the GSPMD
  variant, where the partitioner inserts the norm collectives).

Memory: per-device optimizer state drops ~N× on an N-way mesh — for
Adam (two f32 slots) on an f32 model that is the difference between 2×
model size per device and 2×/N.  Params themselves stay replicated
(that is ZeRO-3 / ``fsdp.py``'s job); ZeRO-1 is the sweet spot when
params fit but the optimizer copies hurt, at DP-identical step math.

Usage::

    state, shardings = zero1_state(params, opt, mesh)
    step = make_train_step_zero1(loss_fn, opt, mesh, shardings)
    eval_step = dp.make_eval_step(loss_fn, mesh, state_shardings=shardings)

With ``optim.with_ema`` the shadow params are flat-sharded like every
other slot — read them with :func:`zero1_ema_params` (plain
``optim.ema_params`` would hand back 1-D padded slices).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import mesh as mesh_lib
from ..optim import Optimizer
from . import collectives, dp

__all__ = [
    "zero1_optimizer",
    "zero1_state",
    "zero1_state_shardings",
    "zero1_ema_params",
    "make_train_step_zero1",
    "make_train_step_zero1_shardmap",
    "per_device_state_bytes",
]


def _is_none(x):
    return x is None


def _flatten_leaf(x, nshards: int):
    """Ravel to 1-D and zero-pad to a multiple of ``nshards``.

    Padding zeros are inert through every elementwise rule shipped in
    ``optim``: grad 0 keeps momentum/Adam slots at 0, so the padded tail
    never changes and never contaminates the real entries.  (Norm-based
    rules see the same norms too — zeros contribute nothing.)
    """
    flat = jnp.ravel(x)
    pad = (-flat.size) % nshards
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


def _flatten_tree(tree, nshards: int):
    return jax.tree.map(
        lambda x: None if x is None else _flatten_leaf(x, nshards),
        tree,
        is_leaf=_is_none,
    )


def _unflatten_like(flat_tree, template):
    """Invert ``_flatten_tree``: drop the pad, restore each leaf's shape."""
    return jax.tree.map(
        lambda f, p: None if p is None else f[: p.size].reshape(p.shape),
        flat_tree,
        template,
        is_leaf=_is_none,
    )


def zero1_optimizer(
    inner: Optimizer, mesh: Mesh, axis: str = mesh_lib.DATA_AXIS
) -> Optimizer:
    """Wrap ``inner`` so its state and update compute shard 1/N over
    ``axis`` (the GSPMD variant).

    ``init`` initializes the inner rule on the FLATTENED-padded param
    tree (state leaves come out flat).  ``update`` constrains the
    flattened gradients to ``P(axis)`` — under ``jit`` that single
    annotation converts the gradient all-reduce into a reduce-scatter
    and shards every downstream update op — then constrains the updated
    flat params back to replicated (the all-gather) and restores leaf
    shapes.  Pure and jit-compatible like every ``optim`` rule.
    """
    n = mesh.shape[axis]
    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())

    def constrain(tree, sh):
        return jax.tree.map(
            lambda x: None if x is None else jax.lax.with_sharding_constraint(x, sh),
            tree,
            is_leaf=_is_none,
        )

    def init(params):
        return inner.init(_flatten_tree(params, n))

    def update(params, grads, state, step):
        flat_p = constrain(_flatten_tree(params, n), shard)
        # the reduce-scatter point: annotating the flat grad P(axis)
        # makes XLA materialize only this device's summed slice
        flat_g = constrain(_flatten_tree(grads, n), shard)
        new_flat_p, new_state = inner.update(flat_p, flat_g, state, step)
        # the all-gather point: the updated slices rejoin as replicated
        new_flat_p = constrain(new_flat_p, repl)
        return _unflatten_like(new_flat_p, params), new_state

    return Optimizer(init, update, name=f"zero1({inner.name})")


def _opt_leaf_spec(x, axis: str, n: int) -> P:
    """P(axis) for leaves whose leading dim splits evenly over the axis
    (every leaf ``zero1_optimizer`` produces); P() otherwise (scalar or
    non-divisible slots a custom rule might carry).  The single rule both
    step variants derive their optimizer-state layout from."""
    shape = np.shape(x)
    divisible = len(shape) >= 1 and shape[0] > 0 and shape[0] % n == 0
    return P(axis) if divisible else P()


def _opt_leaf_sharding(mesh: Mesh, axis: str):
    n = mesh.shape[axis]

    def leaf(x):
        if x is None:
            return None
        return NamedSharding(mesh, _opt_leaf_spec(x, axis, n))

    return leaf


def zero1_state_shardings(
    state: dp.TrainState, mesh: Mesh, axis: str = mesh_lib.DATA_AXIS
) -> dp.TrainState:
    """A ``TrainState`` of ``NamedSharding``s for a ZeRO-1 state: params,
    mutable model state and the step counter replicated; flat optimizer
    state sharded over ``axis`` (any non-divisible or scalar slot —
    none are produced by ``zero1_optimizer``, but custom rules may —
    stays replicated)."""
    repl = NamedSharding(mesh, P())
    return dp.TrainState(
        params=jax.tree.map(lambda _: repl, state.params, is_leaf=_is_none),
        opt_state=jax.tree.map(
            _opt_leaf_sharding(mesh, axis), state.opt_state, is_leaf=_is_none
        ),
        model_state=jax.tree.map(lambda _: repl, state.model_state),
        step=repl,
    )


def zero1_state(
    params,
    optimizer: Optimizer,
    mesh: Mesh,
    axis: str = mesh_lib.DATA_AXIS,
    model_state=None,
) -> tuple[dp.TrainState, dp.TrainState]:
    """Create and place a ZeRO-1 ``TrainState``.

    Returns ``(state, shardings)``: params/model-state replicated,
    optimizer state initialized FLAT by ``zero1_optimizer(optimizer)``
    and distributed 1/N over ``axis``.  Both step variants consume this
    same layout, and orbax checkpoints restore onto it shard-by-shard
    (``load_checkpoint`` takes each target leaf's sharding).
    """
    from ..sharding import unaliased

    z = zero1_optimizer(optimizer, mesh, axis)
    state = dp.TrainState.create(params, z, model_state=model_state)
    shardings = zero1_state_shardings(state, mesh, axis)
    state = jax.tree.map(
        lambda x, s: x if x is None else jax.device_put(unaliased(x), s),
        state,
        shardings,
        is_leaf=_is_none,
    )
    return state, shardings


def make_train_step_zero1(
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    shardings: dp.TrainState,
    axis: str = mesh_lib.DATA_AXIS,
    donate: bool = True,
    accum_steps: int = 1,
    seed: int = 0,
    steps_per_call: int = 1,
    guard: bool = False,
):
    """The DP train step with a ZeRO-1 sharded weight update (GSPMD).

    Identical loss/gradient math to ``dp.make_train_step`` — the wrapped
    optimizer changes only the update's data layout, so every DP feature
    (gradient accumulation, the scan-K device loop, donation, OOM-skip
    at the trainer, the ``guard`` anomaly sentinel) composes unchanged.  ``shardings`` is the tree from
    :func:`zero1_state` and is REQUIRED: compiling without it would fall
    back to dp's replicated default, which silently re-replicates the
    optimizer state on the first step — the exact redundancy ZeRO-1
    exists to remove.
    """
    if shardings is None:
        raise ValueError(
            "make_train_step_zero1 needs the sharding tree from "
            "zero1_state(...): without it the state compiles replicated "
            "and the 1/N optimizer-memory saving silently disappears"
        )
    z = zero1_optimizer(optimizer, mesh, axis)
    return dp.make_train_step(
        loss_fn, z, mesh,
        axis=axis, donate=donate, accum_steps=accum_steps, seed=seed,
        state_shardings=shardings, steps_per_call=steps_per_call,
        guard=guard,
    )


def make_train_step_zero1_shardmap(
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    state: dp.TrainState,
    axis: str = mesh_lib.DATA_AXIS,
    donate: bool = True,
    seed: int = 0,
):
    """Explicit-collectives ZeRO-1: the paper's schedule, written out.

    Per device inside one ``shard_map``: local gradients on the batch
    shard → ``reduce_scatter`` (each device receives the summed 1/N
    flat slice) → the inner optimizer updates THAT SLICE against its
    local flat param/state slice → ``all_gather`` rebuilds the
    replicated params.  The literal analog of the reference's
    sync-then-update loop with the redundant N-fold update sheared off.

    ``state`` (from :func:`zero1_state`) supplies the optimizer-state
    tree structure for the shard_map specs.  Elementwise update rules
    only: a slice-local update cannot reproduce LARS layer norms or
    global-norm clipping — use the GSPMD variant for those.
    """
    for frag in ("lars", "clip"):
        if frag in optimizer.name:
            raise ValueError(
                f"optimizer {optimizer.name!r} needs cross-slice reductions "
                "(layer/global norms); the shard_map ZeRO-1 variant updates "
                "each 1/N slice locally — use make_train_step_zero1 (GSPMD), "
                "where XLA inserts the norm collectives"
            )
    nshards = mesh.shape[axis]
    with_rng = dp._accepts_rng(loss_fn)
    repl_spec = P()
    shard_spec = P(axis)
    state_specs = dp.TrainState(
        params=jax.tree.map(lambda _: repl_spec, state.params, is_leaf=_is_none),
        # same divisibility rule as zero1_state_shardings, so the specs
        # always agree with how zero1_state placed the leaves
        opt_state=jax.tree.map(
            lambda x: None if x is None else _opt_leaf_spec(x, axis, nshards),
            state.opt_state,
            is_leaf=_is_none,
        ),
        model_state=jax.tree.map(lambda _: repl_spec, state.model_state),
        step=repl_spec,
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(state_specs, shard_spec),
        out_specs=(state_specs, repl_spec),
        check_vma=False,
    )
    def step(state: dp.TrainState, batch):
        def lossf(params):
            if with_rng:
                rng = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(seed), state.step),
                    jax.lax.axis_index(axis),
                )
                return loss_fn(params, state.model_state, batch, True, rng=rng)
            return loss_fn(params, state.model_state, batch, True)

        (loss, (new_mstate, _)), grads = jax.value_and_grad(lossf, has_aux=True)(
            state.params
        )
        loss = jax.lax.pmean(loss, axis)
        new_mstate = collectives.pmean(new_mstate, axis)
        # ZeRO-1 gradient exchange: sum-reduce-scatter the flat padded
        # grads, then mean — each device holds grad slice i of N at half
        # the wire bytes of DP's all-reduce.  check_vma=False means the
        # tracer reduces nothing implicitly (grads are device-local), so
        # the reduce_scatter below is THE gradient collective; explicit
        # because the schedule is the point of this variant.
        i = jax.lax.axis_index(axis)

        def local_chunk(tree):
            """Slice i of N from each flat padded leaf."""
            return jax.tree.map(
                lambda x: None if x is None else jax.lax.dynamic_slice_in_dim(
                    x, i * (x.shape[0] // nshards), x.shape[0] // nshards
                ),
                tree,
                is_leaf=_is_none,
            )

        flat_g = collectives.reduce_scatter(
            _flatten_tree(grads, nshards), axis)
        flat_g = jax.tree.map(
            lambda g: None if g is None else g / nshards, flat_g, is_leaf=_is_none
        )
        # this device's param slice, matching its optimizer-state slice
        flat_p = local_chunk(_flatten_tree(state.params, nshards))
        new_flat_p, new_opt = optimizer.apply(
            flat_p, flat_g, state.opt_state, state.step
        )
        # rebuild replicated params from the N updated slices
        gathered = collectives.all_gather(new_flat_p, axis)
        new_params = _unflatten_like(gathered, state.params)
        new_state = dp.TrainState(
            params=new_params,
            opt_state=new_opt,
            model_state=new_mstate,
            step=state.step + 1,
        )
        return new_state, {"loss": loss}

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def zero1_ema_params(state: dp.TrainState):
    """The EMA shadow parameters from a ZeRO-1 state whose optimizer is
    ``optim.with_ema(...)``, restored to model shapes.

    Under ZeRO-1 the shadow lives FLAT-padded and data-sharded like every
    other optimizer slot, so ``optim.ema_params`` alone returns 1-D
    padded slices a model cannot consume — this helper unflattens them
    against the state's params.  Evaluate via e.g.
    ``dataclasses.replace(state, params=zero1_ema_params(state))``.
    """
    from ..optim import ema_params

    return _unflatten_like(ema_params(state.opt_state), state.params)


def per_device_state_bytes(tree) -> dict:
    """Addressable bytes of ``tree`` held per device — the accounting
    used to verify the ~N× optimizer-memory saving (tests and the bench
    report both read it).  Returns ``{device: bytes}``."""
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        seen = set()
        for s in leaf.addressable_shards:
            # replicated leaves surface one shard per device; count each
            # device's copy, but a device only once per leaf
            if s.device in seen:
                continue
            seen.add(s.device)
            out[s.device] = out.get(s.device, 0) + s.data.nbytes
    return out
