"""Pipeline parallelism: GPipe-style microbatch pipelining over a
``pipe`` mesh axis.

Net-new scope beyond the reference (SURVEY §2: "PP: NO"), built the
TPU-idiomatic way: the schedule is a ``lax.scan`` over ticks inside one
``shard_map`` program — device *s* applies stage *s* and hands its
activation to device *s+1* with a ``ppermute`` each tick, so stage
compute overlaps neighbor-to-neighbor ICI transfers.  The backward pass
is not hand-written: differentiating through ``scan`` + ``ppermute``
yields the reverse pipeline schedule automatically (the transpose of a
``ppermute`` is the reverse permutation).

Model contract: one ``stage_fn(params, x) -> y`` applied on every pipe
device with that device's slice of the stacked stage parameters;
activations keep one shape across stages (the ``d_model``
residual-stream invariant transformers already satisfy).  Stages may be
*heterogeneous in behavior*: a ``stage_fn(params, x, stage) -> y``
signature receives the stage index (a traced scalar) and may
``lax.switch`` on it — ``switch_stage([f0, f1, ...])`` builds exactly
that from per-stage callables.  Parameters stay structurally identical
across stages: give every stage the superset parameter tree (unused
leaves still occupy their stage's memory, so keep supersets lean).
Embed/head layers that change the activation shape compose outside the
pipelined middle.

Schedule shape: M microbatches through S stages take M + S - 1 ticks;
the (S-1)/(M+S-1) bubble shrinks as M grows — pick ``num_microbatches >=
2*S`` in production.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import Optimizer
from .dp import TrainState

Pytree = Any

__all__ = [
    "pipeline_apply",
    "make_train_step_pp",
    "stack_stage_params",
    "switch_stage",
    "chunk_stages",
]

# sourced from the device layer's single declaration (lint rule FDT105:
# a re-declared literal drifts silently on rename); re-exported here for
# the callers that import it from the pp module
from ..mesh import PIPE_AXIS


def _accepts_stage(fn: Callable) -> bool:
    """Does ``fn`` require a third positional arg (the stage index)?

    Deliberately strict: only callables with >= 3 *non-defaulted*
    positional parameters opt in.  A defaulted third parameter
    (``def f(p, x, scale=0.5)``) or ``*args`` must NOT silently receive
    the traced stage index — that would corrupt previously-valid
    two-argument stage functions.  ``switch_stage`` is the explicit
    opt-in for heterogeneous pipelines.
    """
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    required = [
        p for p in sig.parameters.values()
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
        and p.default is p.empty
    ]
    return len(required) >= 3


def chunk_stages(stage_fn: Callable, counts=None,
                 axis: str = PIPE_AXIS) -> Callable:
    """Host V consecutive logical stages per pipe device (blocked virtual
    pipeline): wraps ``stage_fn`` to ``lax.scan`` over a leading chunk
    dim in its params, so device *s* applies logical stages
    ``s·V … s·V+V-1`` in sequence each tick.

    Build the params by stacking ALL ``V·S`` per-stage trees, reshaping
    each leaf to ``(S, V, ...)``, and sharding the leading dim on the
    pipe axis (``stack_stage_params`` of per-device ``(V, ...)`` trees
    does exactly that).

    ``counts`` (one int per pipe device) turns on NON-uniform splits —
    the profile-guided planner's output (``parallel/pp_plan.py``):
    every device's param slab is padded to ``max(counts)`` chunks, and
    device *i* applies only its first ``counts[i]`` per tick — the rest
    are ``lax.cond``-skipped identity chunks (their zero params are
    never touched, their grads stay zero).  The counts table is
    trace-time STATIC (baked like the 1F1B schedule tables, read per
    device via ``axis_index``), so a plan change recompiles exactly
    like a depth change would — it never enters a jit argument
    signature, and within a run there is still exactly ONE compile.

    Under the GPipe schedule, blocked placement keeps the bubble at
    ``(S-1)/(M+S-1)`` ticks (each tick is V stage-times) — the same
    relative bubble as a V-times-deeper per-device stage, which is what
    it is.  Interleaved (Megatron 1F1B) placement is not implemented
    here: the backward is AD-derived from the forward scan, so there is
    no hand-written 1F1B schedule to interleave.
    """
    if counts is None:
        def fn(params, x):
            h, _ = jax.lax.scan(lambda h, p: (stage_fn(p, h), None), x, params)
            return h

        return fn

    import numpy as np

    counts_arr = np.asarray(list(counts), np.int32)

    def fn(params, x):
        mine = jnp.take(jnp.asarray(counts_arr), jax.lax.axis_index(axis))
        vmax = jax.tree.leaves(params)[0].shape[0]

        def body(h, pc):
            p, c = pc
            h2 = jax.lax.cond(
                c < mine,
                lambda p_, h_: stage_fn(p_, h_),
                lambda p_, h_: h_,
                p, h)
            return h2, None

        h, _ = jax.lax.scan(
            body, x, (params, jnp.arange(vmax, dtype=jnp.int32)))
        return h

    return fn


def switch_stage(stage_fns: list) -> Callable:
    """Compose per-stage callables into one ``stage_fn(params, x, stage)``
    that ``lax.switch``es on the (traced) stage index — the heterogeneous
    pipeline form.  Every callable must accept the same params structure
    (use a superset tree) and preserve the activation shape.

    The callable records ``len(stage_fns)`` so ``pipeline_apply`` can
    reject a list whose length does not match the pipeline's stage count
    (``lax.switch`` clamps out-of-range indices, which would otherwise
    silently reuse the last stage)."""

    branches = [lambda p, x, f=f: f(p, x) for f in stage_fns]

    def fn(params, x, stage):
        return jax.lax.switch(stage, branches, params, x)

    fn._num_stage_fns = len(stage_fns)
    return fn


def stack_stage_params(per_stage: list, mesh: Mesh, axis: str = PIPE_AXIS) -> Pytree:
    """Stack S per-stage param trees along a new leading dim sharded over
    the ``pipe`` axis — stage s's params live on pipe device s."""
    from ..sharding import stack_on_axis

    return stack_on_axis(per_stage, mesh, axis)


def pipeline_apply(
    stage_fn: Callable,
    mesh: Mesh,
    axis: str = PIPE_AXIS,
    num_microbatches: Optional[int] = None,
    batch_axis: Optional[str] = None,
    remat: bool = False,
):
    """Build ``fwd(stacked_params, x) -> y`` running the GPipe schedule.

    ``stacked_params`` leaves have leading dim S sharded on ``axis``;
    ``x`` is the batch (replicated input spec — only stage 0 reads it;
    the compiler keeps the unused copies unrealized).  Output is the
    last stage's activations, same batch layout as the input.

    ``batch_axis`` composes data parallelism with the pipeline on a 2-D
    ``(data, pipe)`` mesh: ``x``'s leading dim is sharded over
    ``batch_axis`` and each data-parallel row of the mesh pipelines its
    own shard (microbatch count M divides the per-shard batch).

    ``remat=True`` wraps the per-tick stage apply in ``jax.checkpoint``:
    the backward scan then stores only each tick's stage INPUT and
    recomputes the stage internals — per-device activation memory drops
    from O(ticks · stage-internals) to O(ticks · microbatch), the same
    memory effect 1F1B targets, obtained without a hand-written
    schedule (the AD-derived reverse pipeline is unchanged).  Cost: one
    extra stage forward per tick in the backward pass.
    """
    S = mesh.shape[axis]
    M = num_microbatches or S
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    with_stage = _accepts_stage(stage_fn)
    n_fns = getattr(stage_fn, "_num_stage_fns", None)
    if remat:
        # wrap AFTER signature/attr inspection: jax.checkpoint obscures
        # both.  prevent_cse=False: the wrapped fn runs inside lax.scan,
        # where the CSE-prevention barriers are unnecessary (per the
        # jax.checkpoint docs) and only hinder XLA fusion
        stage_fn = jax.checkpoint(stage_fn, prevent_cse=False)
    if n_fns is not None and n_fns != S:
        raise ValueError(
            f"switch_stage got {n_fns} stage fns but the '{axis}' axis has "
            f"{S} stages (lax.switch would silently clamp the stage index)"
        )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(batch_axis)),
        out_specs=P(batch_axis),
    )
    def run(stacked_params, x):
        params = jax.tree.map(lambda p: p[0], stacked_params)  # my stage's slice
        idx = jax.lax.axis_index(axis)
        b = x.shape[0]
        assert b % M == 0, f"batch {b} not divisible by {M} microbatches"
        mb = x.reshape(M, b // M, *x.shape[1:])
        # mark the stream device-varying up front: the scan carry crosses
        # a ppermute, so its type must be varying over the pipe axis from
        # the start (shard_map's VMA typing)
        mb = jax.lax.pcast(mb, axis, to="varying")
        zero = jnp.zeros_like(mb[0])

        def tick(state, t):
            # stage 0 feeds microbatch t (while any remain); later stages
            # consume the activation ppermuted in last tick
            feed = jax.lax.dynamic_index_in_dim(
                mb, jnp.minimum(t, M - 1), 0, keepdims=False
            )
            x_in = jnp.where(idx == 0, jnp.where(t < M, feed, zero), state)
            y = stage_fn(params, x_in, idx) if with_stage else stage_fn(params, x_in)
            # the last stage's result for microbatch t-(S-1) is ready
            out = jnp.where(idx == S - 1, y, jnp.zeros_like(y))
            state_next = jax.lax.ppermute(y, axis, fwd_perm)
            return state_next, out

        _, outs = jax.lax.scan(tick, zero, jnp.arange(M + S - 1))
        outs = outs[S - 1 :]  # (M, mb, ...) valid last-stage outputs
        # all-reduce broadcasts the last stage's outputs (others are zero)
        outs = jax.lax.psum(outs, axis)
        return outs.reshape(b, *outs.shape[2:])

    return run


def make_train_step_pp(
    stage_fn: Callable,
    loss: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    axis: str = PIPE_AXIS,
    num_microbatches: Optional[int] = None,
    donate: bool = True,
    remat: bool = False,
):
    """Compile a full pipelined training step.

    ``loss(y, labels)`` consumes the pipeline output.  Params and
    optimizer state stay stage-sharded on ``axis``; gradients arrive
    stage-sharded for free (the AD transpose of the stacked-slice read),
    so the optimizer update is local to each pipe device — no gradient
    collective at all, the pipeline's communication is activations only.
    """
    from ..sharding import make_shardings
    from .rules import train_state_specs

    fwd = pipeline_apply(
        stage_fn, mesh, axis=axis, num_microbatches=num_microbatches, remat=remat
    )
    repl = NamedSharding(mesh, P())

    def state_shardings(state: TrainState) -> TrainState:
        p_specs = jax.tree.map(lambda _: P(axis), state.params)
        return make_shardings(train_state_specs(state, p_specs), mesh)

    def step(state: TrainState, batch):
        def lossf(params):
            y = fwd(params, batch["image"])
            return loss(y, batch["label"])

        lval, grads = jax.value_and_grad(lossf)(state.params)
        new_params, new_opt = optimizer.apply(
            state.params, grads, state.opt_state, state.step
        )
        new_state = TrainState(
            params=new_params,
            opt_state=new_opt,
            model_state=state.model_state,
            step=state.step + 1,
        )
        return new_state, {"loss": lval}

    def compile_for(state: TrainState):
        sh = state_shardings(state)
        return jax.jit(
            step,
            in_shardings=(sh, repl),
            out_shardings=(sh, repl),
            donate_argnums=(0,) if donate else (),
        )

    return compile_for
