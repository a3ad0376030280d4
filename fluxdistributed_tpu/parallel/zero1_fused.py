"""Fused ZeRO-1 weight update: pack → ONE reduce-scatter → ONE fused
Adam kernel → ONE all-gather.

``zero1.make_train_step_zero1_shardmap`` executes the paper's schedule
(arXiv:2004.13336) faithfully, but as a *per-leaf* composition: every
parameter leaf gets its own reduce-scatter, its own chain of Adam
element ops (2 multiplies + 2 FMAs + rsqrt + divide + subtract, each a
separate HLO with its own HBM round-trip unless fusion wins), and its
own all-gather.  On a transformer that is hundreds of small collectives
and kernels per step — exactly the launch/latency overhead the
full-program-compilation premise (arXiv:1810.09868) says to fuse away.

This module collapses the whole update into four programs, total:

1. **pack** — every gradient leaf is raveled, cast to f32, and
   concatenated into ONE flat buffer, zero-padded so it splits evenly
   over the data axis (pad entries are inert through Adam: zero grad →
   zero momentum → zero delta);
2. **one reduce-scatter** on that buffer (vs one per leaf) — each
   device receives the summed 1/N slice;
3. **one fused Adam kernel** (``ops``-style Pallas, NEW
   ``fused_adam_update``) over the local slice: p/g/m/v stream through
   VMEM once, the full m/v/p̂ chain runs on the VPU between the loads
   and the stores — 4 reads + 3 writes of HBM, the streaming minimum;
4. **one all-gather**, then unpack back to leaf shapes.

Off TPU the kernel body runs as the identical jnp expression (the
"xla" impl — same math, same f32 accumulation, so CPU tests pin
bit-for-bit parity against ``make_train_step_zero1``), and the Pallas
interpreter covers the real kernel code in the slow tier.

Optimizer state is two flat f32 buffers (``{"m", "v"}``) sharded
``P(data)`` — checkpointing sees an ordinary (if flat) state tree.
The update math is bakes-Adam-only by design: the fusion IS the rule.
For other rules use the composable ``zero1`` variants.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import mesh as mesh_lib
from ..ops.pallas_attention import interpret_mode
from . import collectives, dp

__all__ = [
    "fused_adam_update",
    "pack_tree",
    "unpack_tree",
    "zero1_fused_state",
    "make_train_step_zero1_fused",
]

_LANES = 128
_SUBLANES = 8
#: the packed buffer pads to a multiple of (shards × one f32 tile) so
#: every device's slice reshapes to whole [8, 128] VPU tiles
_TILE = _LANES * _SUBLANES

def _resolve_impl(impl: str | None) -> str:
    """``None``/``"auto"`` → compiled kernel on TPU, the identical-math
    XLA expression elsewhere; ``"interpret"`` runs the real kernel under
    the Pallas interpreter (the CPU kernel-parity tests)."""
    if impl in (None, "auto"):
        return "pallas" if not interpret_mode() else "xla"
    if impl not in ("pallas", "interpret", "xla"):
        raise ValueError(f"unknown impl {impl!r} (pallas|interpret|xla|auto)")
    return impl


def _is_none(x):
    return x is None


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------


def packed_size(params, nshards: int) -> int:
    """Flat f32 element count of the packed tree, padded to split into
    whole VPU tiles per shard."""
    total = sum(l.size for l in jax.tree.leaves(params, is_leaf=_is_none)
                if l is not None)
    return total + (-total) % (nshards * _TILE)


def pack_tree(tree, nshards: int) -> jax.Array:
    """Ravel + concat every (non-``None``) leaf into one padded f32
    buffer — the single operand the collectives and the kernel see."""
    leaves = [l for l in jax.tree.leaves(tree, is_leaf=_is_none)
              if l is not None]
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    pad = (-flat.size) % (nshards * _TILE)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


def unpack_tree(flat: jax.Array, template):
    """Invert :func:`pack_tree` against ``template``'s shapes/dtypes
    (the pad tail is dropped)."""
    leaves, treedef = jax.tree.flatten(template, is_leaf=_is_none)
    out, off = [], 0
    for leaf in leaves:
        if leaf is None:
            out.append(None)
            continue
        out.append(flat[off:off + leaf.size].reshape(leaf.shape)
                   .astype(leaf.dtype))
        off += leaf.size
    return treedef.unflatten(out)


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------


def _adam_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *, b1, b2, eps):
    """One [block, 128] tile of the fused Adam chain — the entire
    m/v/bias-correct/apply sequence between one set of loads and one
    set of stores.  ``sc_ref`` (scalar-prefetch): [eta, c1, c2] f32 —
    the step-dependent scalars, data so LR schedules never retrace."""
    eta, c1, c2 = sc_ref[0], sc_ref[1], sc_ref[2]
    g = g_ref[:]
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * (g * g)
    mhat = m / c1
    vhat = v / c2
    po_ref[:] = p_ref[:] - eta * mhat / (jnp.sqrt(vhat) + eps)
    mo_ref[:] = m
    vo_ref[:] = v


@functools.partial(
    jax.jit, static_argnames=("b1", "b2", "eps", "impl", "block_rows"))
def _fused_adam_impl(p, g, m, v, scalars, b1, b2, eps, impl, block_rows):
    n = p.shape[0]
    if impl == "xla":
        # the kernel body as one XLA expression — identical math (and
        # the op order of optim.adam's step_leaf, so parity with the
        # composable ZeRO-1 variants is exact)
        eta, c1, c2 = scalars[0], scalars[1], scalars[2]
        m2 = b1 * m + (1.0 - b1) * g
        v2 = b2 * v + (1.0 - b2) * (g * g)
        p2 = p - eta * (m2 / c1) / (jnp.sqrt(v2 / c2) + eps)
        return p2, m2, v2

    rows = n // _LANES
    # block_rows must DIVIDE rows or the grid would drop the tail rows
    # (leaving uninitialized p'/m'/v' to be all-gathered into params).
    # rows is a multiple of _SUBLANES by the pack alignment, so stepping
    # down in whole sublanes always terminates at a valid tile-aligned
    # divisor (worst case _SUBLANES itself).
    block_rows = max(min(block_rows, rows) // _SUBLANES * _SUBLANES,
                     _SUBLANES)
    while rows % block_rows:
        block_rows -= _SUBLANES
    shape2 = (rows, _LANES)
    spec = pl.BlockSpec((block_rows, _LANES), lambda i, sc: (i, 0))
    p2, m2, v2 = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // block_rows,),
            in_specs=[spec] * 4,
            out_specs=[spec] * 3,
        ),
        out_shape=[jax.ShapeDtypeStruct(shape2, jnp.float32)] * 3,
        interpret=impl == "interpret",
    )(scalars, p.reshape(shape2), g.reshape(shape2),
      m.reshape(shape2), v.reshape(shape2))
    return p2.reshape(n), m2.reshape(n), v2.reshape(n)


def fused_adam_update(
    p: jax.Array,
    g: jax.Array,
    m: jax.Array,
    v: jax.Array,
    step,
    *,
    lr=1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    impl: str | None = None,
    block_rows: int = 512,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The fused Adam step over flat f32 buffers (a local ZeRO-1 shard):
    ``(p', m', v')``.  ``lr`` may be a schedule (callable on ``step``);
    the step-dependent scalars ride as DATA so nothing retraces across
    steps.  Buffer length must be a multiple of 1024 (whole VPU tiles —
    :func:`pack_tree` guarantees it)."""
    if p.shape[0] % _TILE:
        raise ValueError(
            f"fused_adam_update needs whole [{_SUBLANES}, {_LANES}] tiles: "
            f"length {p.shape[0]} is not a multiple of {_TILE} "
            "(pack with pack_tree)")
    eta = lr(step) if callable(lr) else lr
    t = jnp.asarray(step, jnp.float32) + 1.0
    scalars = jnp.stack([
        jnp.asarray(eta, jnp.float32),
        1.0 - jnp.power(jnp.float32(b1), t),
        1.0 - jnp.power(jnp.float32(b2), t),
    ])
    return _fused_adam_impl(
        p.astype(jnp.float32), g.astype(jnp.float32),
        m.astype(jnp.float32), v.astype(jnp.float32), scalars,
        b1=b1, b2=b2, eps=eps, impl=_resolve_impl(impl),
        block_rows=block_rows)


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------


def zero1_fused_state(
    params,
    mesh: Mesh,
    axis: str = mesh_lib.DATA_AXIS,
    model_state=None,
) -> tuple[dp.TrainState, dp.TrainState]:
    """Create and place the fused-update ``TrainState``: params and
    model state replicated, optimizer state as TWO flat f32 buffers
    (``m``/``v`` over the packed layout) sharded 1/N over ``axis`` —
    the same memory win as ``zero1_state``, minus the per-leaf tree."""
    from ..sharding import unaliased

    n = mesh.shape[axis]
    size = packed_size(params, n)
    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    # unaliased: device_put onto the resident layout can return the
    # CALLER's buffers — a donated step would then delete them under
    # the caller (the same guard zero1_state uses)
    state = dp.TrainState(
        params=jax.tree.map(
            lambda x: None if x is None else jax.device_put(
                unaliased(x), repl),
            params, is_leaf=_is_none),
        opt_state={
            "m": jax.device_put(jnp.zeros((size,), jnp.float32), shard),
            "v": jax.device_put(jnp.zeros((size,), jnp.float32), shard),
        },
        model_state=jax.tree.map(
            lambda x: jax.device_put(unaliased(x), repl), model_state or {}),
        step=jax.device_put(jnp.zeros((), jnp.int32), repl),
    )
    shardings = dp.TrainState(
        params=jax.tree.map(lambda _: repl, state.params, is_leaf=_is_none),
        opt_state={"m": shard, "v": shard},
        model_state=jax.tree.map(lambda _: repl, state.model_state),
        step=repl,
    )
    return state, shardings


def make_train_step_zero1_fused(
    loss_fn: Callable,
    mesh: Mesh,
    state: dp.TrainState,
    *,
    lr=1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    axis: str = mesh_lib.DATA_AXIS,
    donate: bool = True,
    seed: int = 0,
    impl: str | None = None,
):
    """ZeRO-1 with the fused packed update: per device inside ONE
    ``shard_map`` — local grads on the batch shard → pack the whole
    gradient tree flat → ONE reduce-scatter → the fused Adam kernel on
    this device's slice → ONE all-gather → unpack.  Numerically the
    same summed-gradient Adam step as ``make_train_step_zero1`` (in
    f32; an f32 model matches bit-for-bit), at collective/kernel counts
    independent of the number of parameter leaves.

    ``state`` comes from :func:`zero1_fused_state` and fixes the spec
    tree; ``lr`` may be a schedule.
    """
    nshards = mesh.shape[axis]
    with_rng = dp._accepts_rng(loss_fn)
    repl_spec = P()
    shard_spec = P(axis)
    state_specs = dp.TrainState(
        params=jax.tree.map(lambda _: repl_spec, state.params,
                            is_leaf=_is_none),
        opt_state={"m": shard_spec, "v": shard_spec},
        model_state=jax.tree.map(lambda _: repl_spec, state.model_state),
        step=repl_spec,
    )
    @functools.partial(
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

        (loss, (new_mstate, _)), grads = jax.value_and_grad(
            lossf, has_aux=True)(state.params)
        loss = jax.lax.pmean(loss, axis)
        new_mstate = collectives.pmean(new_mstate, axis)
        flat_g = pack_tree(grads, nshards)
        i = jax.lax.axis_index(axis)
        chunk = flat_g.shape[0] // nshards
        # check_vma=False: grads are device-local (no implicit psum of
        # the replicated-param cotangent), so this is THE gradient
        # collective — ONE for the whole tree (the fusion's wire half)
        flat_g = collectives.reduce_scatter({"g": flat_g}, axis)["g"]
        flat_g = flat_g / nshards
        flat_p = jax.lax.dynamic_slice_in_dim(
            pack_tree(state.params, nshards), i * chunk, chunk)
        p2, m2, v2 = fused_adam_update(
            flat_p, flat_g, state.opt_state["m"], state.opt_state["v"],
            state.step, lr=lr, b1=b1, b2=b2, eps=eps, impl=impl)
        gathered = collectives.all_gather({"p": p2}, axis)["p"]
        new_params = unpack_tree(gathered, state.params)
        new_state = dp.TrainState(
            params=new_params,
            opt_state={"m": m2, "v": v2},
            model_state=new_mstate,
            step=state.step + 1,
        )
        return new_state, {"loss": loss}

    return jax.jit(step, donate_argnums=(0,) if donate else ())
