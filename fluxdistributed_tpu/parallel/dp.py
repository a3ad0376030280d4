"""Data-parallel training steps — the reference's core feature, compiled.

Replaces the reference's task-DDP hot path (SURVEY §3.2): where the
reference spawns one Julia Task per GPU for ``train_step`` (Zygote
gradient + DtoD push into a HOST-resident buffer, src/ddp_tasks.jl:80-84),
barriers, hub-reduces (``sync_buffer`` :93-109), and runs one replicated
optimizer step per device (``update`` :163-172), here the whole
step — forward, backward, gradient all-reduce, optimizer update — is ONE
jitted SPMD program over a ``jax.sharding.Mesh``:

* parameters/optimizer state are *replicated* (NamedSharding ``P()``),
* the batch is *sharded* on the ``data`` axis (``P('data')``),
* the loss is a mean over the global batch, so XLA's gradient of that
  mean IS the cross-replica all-reduce — no buffers, no barriers, no
  hub, and the update is computed once and identical on every device
  (the property the reference asserts via ``ensure_synced``
  src/ddp_tasks.jl:115-126 and its replica-identity tests).

Two implementations are provided:

* ``make_train_step`` — idiomatic ``jit`` with sharding annotations
  (production path; XLA inserts collectives).
* ``make_train_step_shardmap`` — explicit per-device SPMD via
  ``shard_map`` + ``pmean`` (the literal analog of the reference's
  per-replica semantics; also the base for pipelines that need manual
  collectives).  Results are numerically identical; tests assert both
  match single-device global-batch training.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import mesh as mesh_lib
from ..optim import Optimizer
from . import collectives

Pytree = Any

__all__ = ["TrainState", "guard_sentinel", "make_train_step",
           "make_eval_step", "make_train_step_shardmap"]


@struct.dataclass
class TrainState:
    """Replicated training state: params + optimizer state + mutable model
    state (e.g. BatchNorm running stats) + step counter.

    The analog of the reference's per-device ``(dev, model)`` pairs plus
    ``sts[dev]`` optimizer states (src/ddp_tasks.jl:273-276) — except
    there is exactly one logical copy, kept replicated by sharding.
    """

    params: Pytree
    opt_state: Pytree
    model_state: Pytree
    step: jnp.ndarray

    @classmethod
    def create(cls, params, optimizer: Optimizer, model_state=None):
        return cls(
            params=params,
            opt_state=optimizer.init(params),
            model_state=model_state if model_state is not None else {},
            step=jnp.zeros((), jnp.int32),
        )


# A loss function has signature
#   loss_fn(params, model_state, batch, train: bool, rng=None)
#       -> (loss, (new_model_state, aux))
# where ``batch`` is any pytree of arrays with a leading batch dim and
# ``rng`` (optional keyword) seeds stochastic layers (dropout/drop-path).
# Four-argument custom loss functions remain supported — the step makers
# only pass ``rng`` when the signature accepts it (``_accepts_rng``).


def _accepts_rng(loss_fn: Callable) -> bool:
    import inspect

    try:
        sig = inspect.signature(loss_fn)
    except (TypeError, ValueError):
        return False
    p = sig.parameters.get("rng")
    return p is not None or any(
        q.kind is inspect.Parameter.VAR_KEYWORD for q in sig.parameters.values()
    )


def flax_loss_fn(model, loss, has_aux_state: bool = True) -> Callable:
    """Adapt a flax.linen module + a loss (e.g. ``logitcrossentropy``) to
    the framework's loss signature.  Handles mutable collections such as
    ``batch_stats`` (BatchNorm running statistics) and stochastic layers
    (``rng`` becomes the ``dropout`` stream, e.g. ViT dropout and
    ConvNeXt stochastic depth)."""

    def fn(params, model_state, batch, train: bool, rng=None):
        x, y = batch["image"], batch["label"]
        variables = {"params": params, **model_state}
        rngs = {"dropout": rng} if (train and rng is not None) else None
        if train and model_state:
            out, mutated = model.apply(
                variables, x, train=True, mutable=list(model_state.keys()), rngs=rngs
            )
            return loss(out, y), (mutated, out)
        out = model.apply(variables, x, train=train, rngs=rngs)
        return loss(out, y), (model_state, out)

    return fn


def guard_sentinel(loss, grads):
    """The in-graph anomaly sentinel (``train/guard.py``): a length-2
    f32 vector ``[poisoned_loss, grad_norm]`` computed where the
    gradients already live, so detecting a bad step costs ONE extra
    device->host scalar fetch and zero extra compiles.

    * ``grad_norm`` — global L2 norm over every gradient leaf (f32
      accumulation).  A NaN anywhere poisons it to NaN; an Inf (or an
      f32-overflowing explosion) drives it to Inf — the global
      ``isfinite`` any-reduce over the gradients, folded into a number
      that is also the magnitude signal.
    * ``poisoned_loss`` — the step loss plus ``0 * grad_norm``: equal
      to the loss bit-for-bit when the gradients are finite (the
      loss-spike detector's input), NaN whenever loss or any gradient
      is not (``0 * inf`` and ``0 * nan`` are both NaN) — loss AND
      gradient finiteness any-reduced into one scalar.
    """
    gsq = jnp.float32(0.0)
    for g in jax.tree.leaves(grads):
        gsq = gsq + jnp.sum(jnp.square(g.astype(jnp.float32)))
    gnorm = jnp.sqrt(gsq)
    return jnp.stack(
        [jnp.asarray(loss, jnp.float32) + 0.0 * gnorm, gnorm])


def make_train_step(
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    axis: str = mesh_lib.DATA_AXIS,
    donate: bool = True,
    accum_steps: int = 1,
    seed: int = 0,
    state_shardings=None,
    steps_per_call: int = 1,
    guard: bool = False,
):
    """Compile the full DP training step under ``jit`` + shardings.

    Returns ``step_fn(state, batch) -> (state, metrics)`` where ``batch``
    arrays are sharded on ``axis`` and ``state`` is replicated.  The
    gradient all-reduce is implicit in differentiating the global-batch
    mean loss.

    ``state_shardings`` (a ``TrainState`` of ``NamedSharding`` leaves)
    overrides the replicated default for the train state — this is how
    ``prepare_training(layout=...)`` turns the same step into ZeRO-style
    fully-sharded (and tensor-parallel) training without duplicating the
    step logic:
    XLA inserts the all-gathers (params on use) and reduce-scatters
    (grads at the sharded update) implied by the annotations.

    ``accum_steps > 1`` enables gradient accumulation (beyond the
    reference, which has no analog): the batch's leading dim is split
    into ``accum_steps`` microbatches processed by a ``lax.scan`` —
    activations for only ONE microbatch are live at a time, so the same
    device memory trains an ``accum_steps``× larger effective batch.
    Gradients are averaged over microbatches (identical semantics to one
    big batch for mean losses); mutable model state (BatchNorm stats)
    threads through the scan sequentially.

    ``seed`` roots the dropout/drop-path stream: two seeds draw different
    masks, the same seed reproduces a run exactly.

    ``steps_per_call > 1`` runs K optimizer steps per dispatch — the
    device loop: the returned function takes batches STACKED on a new
    leading dim ``[K, batch, ...]`` (sharded ``P(None, axis)``, the
    loader's ``chunk=K`` layout) and ``lax.scan``s the step over them,
    returning metrics stacked ``[K]``.  Each step consumes a DIFFERENT
    batch — semantics identical to K separate calls — but the host pays
    one dispatch instead of K, which matters when host dispatch latency
    is large or the host is slow relative to the step.

    A ``loss_fn`` with a ``step_metrics`` attribute (a function of the
    step's new model state to a dict of small arrays) gets those arrays
    into ``metrics`` beside the loss: ``lm_loss_fn`` reports a router's
    load that way, and ``train`` feeds its counters from it when the
    step completes.

    ``guard=True`` adds ``metrics["guard"]`` — the
    :func:`guard_sentinel` ``[poisoned_loss, grad_norm]`` vector (per
    step; stacked ``[K, 2]`` under the device loop), computed in-graph
    from the same gradients the update consumes.  It changes nothing
    about the update math; the trainer's guard policy engine fetches it
    once per step to detect non-finite grads/loss and loss spikes.
    """
    from ..sharding import batch_entry

    repl = NamedSharding(mesh, P())
    # axis=None: batch replicated (e.g. a pure 'expert' mesh where the
    # MoE shard_map does its own token split); a tuple shards the batch
    # dim over several axes jointly (the 3-D (data, fsdp) layouts)
    shard = NamedSharding(mesh, P(batch_entry(axis)) if axis is not None
                          else P())
    state_sh = repl if state_shardings is None else state_shardings
    with_rng = _accepts_rng(loss_fn)
    # what a loss function wants reported of the state its step leaves
    # (``lm_loss_fn``: a router's load, for the trainer's counters); small
    # arrays, computed in the step and never waited for by the loop
    step_metrics = getattr(loss_fn, "step_metrics", None)

    def grad_of(params, mstate, batch, step_idx):
        def lossf(p):
            if with_rng:
                # per-step dropout/drop-path stream rooted at the user
                # seed, identical on every device (replicated state.step
                # → replicated key)
                rng = jax.random.fold_in(jax.random.PRNGKey(seed), step_idx)
                return loss_fn(p, mstate, batch, True, rng=rng)
            return loss_fn(p, mstate, batch, True)

        # metadata only: names forward+backward in a profile (XProf, the
        # trace reducer) without touching the program or its cache key
        with jax.named_scope("fdtpu/grad"):
            return jax.value_and_grad(lossf, has_aux=True)(params)

    def step(state: TrainState, batch):
        if accum_steps == 1:
            (loss, (new_mstate, _)), grads = grad_of(
                state.params, state.model_state, batch, state.step
            )
        else:
            micro = jax.tree.map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:]),
                batch,
            )

            def body(carry, mb):
                mstate, gsum, lsum, i = carry
                (l, (mstate, _)), g = grad_of(
                    state.params, mstate, mb, state.step * accum_steps + i
                )
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (mstate, gsum, lsum + l, i + 1), None

            gzero = jax.tree.map(jnp.zeros_like, state.params)
            (new_mstate, gsum, lsum, _), _ = jax.lax.scan(
                body, (state.model_state, gzero, 0.0, 0), micro
            )
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            loss = lsum / accum_steps
        with jax.named_scope("fdtpu/update"):
            new_params, new_opt = optimizer.apply(
                state.params, grads, state.opt_state, state.step
            )
        new_state = TrainState(
            params=new_params,
            opt_state=new_opt,
            model_state=new_mstate,
            step=state.step + 1,
        )
        metrics = {"loss": loss}
        if step_metrics is not None:
            metrics.update(step_metrics(new_mstate))
        if guard:
            metrics["guard"] = guard_sentinel(loss, grads)
        return new_state, metrics

    if steps_per_call == 1:
        return jax.jit(
            step,
            in_shardings=(state_sh, shard),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,) if donate else (),
        )

    if steps_per_call < 1:
        raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
    chunk_shard = NamedSharding(
        mesh, P(None, batch_entry(axis)) if axis is not None else P())

    def chunked(state: TrainState, batches):
        return jax.lax.scan(step, state, batches)

    return jax.jit(
        chunked,
        in_shardings=(state_sh, chunk_shard),
        out_shardings=(state_sh, repl),
        donate_argnums=(0,) if donate else (),
    )


def make_eval_step(
    loss_fn: Callable,
    mesh: Mesh,
    axis: str = mesh_lib.DATA_AXIS,
    topk: tuple = (1, 5, 10),
    state_shardings=None,
):
    """Compiled eval pass returning ``(loss, metrics)``.

    The analog of ``log_loss_and_acc`` (src/ddp_tasks.jl:128-148), but
    where the reference runs TWO forward passes and pulls the logits to
    host for a partial-sort top-k (``topkaccuracy`` src/utils.jl:39-45),
    here one compiled pass computes loss AND top-k accuracies in-graph
    (``lax.top_k`` on device).  Outputs are replicated scalars, so this
    works unchanged on a multi-host mesh where per-shard logits are not
    host-addressable.
    """
    from ..ops import topkaccuracy
    from ..sharding import batch_entry

    repl = NamedSharding(mesh, P())
    # axis=None: batch replicated (e.g. a pure 'expert' mesh where the
    # MoE shard_map does its own token split); tuples shard jointly
    shard = NamedSharding(mesh, P(batch_entry(axis)) if axis is not None
                          else P())
    state_sh = repl if state_shardings is None else state_shardings

    def step(state: TrainState, batch):
        loss, (_, logits) = loss_fn(state.params, state.model_state, batch, False)
        metrics = {
            f"top{k}": topkaccuracy(logits, batch["label"], k=k) for k in topk
        }
        return loss, metrics

    return jax.jit(step, in_shardings=(state_sh, shard), out_shardings=(repl, repl))


def make_train_step_shardmap(
    loss_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    axis: str = mesh_lib.DATA_AXIS,
    donate: bool = True,
    seed: int = 0,
):
    """Explicit-SPMD DP step: per-device gradients + ``pmean``.

    The literal translation of the reference's semantics — each replica
    computes gradients on its shard (``train_step`` src/ddp_tasks.jl:80-84),
    gradients are mean-reduced across replicas (``sync_buffer`` :93-109 →
    here one ``pmean`` collective), and every replica applies the same
    optimizer update (``update`` :163-172).  Because the averaged gradient
    and the update are computed identically on every device, replicas stay
    bit-identical — the invariant the reference tests
    (test/single_device.jl:160-167).
    """
    repl_spec = P()
    batch_spec = P(axis)
    with_rng = _accepts_rng(loss_fn)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(repl_spec, batch_spec),
        out_specs=(repl_spec, repl_spec),
        check_vma=False,
    )
    def step(state: TrainState, batch):
        def lossf(params):
            if with_rng:
                # distinct stream per device so each batch shard draws
                # independent dropout/drop-path masks, rooted at the
                # user seed
                rng = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(seed), state.step),
                    jax.lax.axis_index(axis),
                )
                return loss_fn(params, state.model_state, batch, True, rng=rng)
            return loss_fn(params, state.model_state, batch, True)

        (loss, (new_mstate, _)), grads = jax.value_and_grad(lossf, has_aux=True)(
            state.params
        )
        # check_vma=False: the tracer does no replication typing, so the
        # gradient w.r.t. the replicated (P()) params is this device's
        # LOCAL gradient and nothing is reduced implicitly.  The mean is
        # therefore one explicit collective — sync_buffer's
        # accumulate-then-divide (src/ddp_tasks.jl:103-106) as a pmean.
        # Explicit over check_vma=True's implicit psum because a loss
        # may contain pallas_call / custom_vjp ops that carry no
        # varying-axes types, and because the collective ledger
        # (obs/comms.py) then reads the schedule as written.
        grads = collectives.pmean(grads, axis)
        loss = jax.lax.pmean(loss, axis)
        # Mutable model state (BatchNorm running stats) is per-shard →
        # average it across replicas so replicas stay identical.
        new_mstate = collectives.pmean(new_mstate, axis)
        new_params, new_opt = optimizer.apply(
            state.params, grads, state.opt_state, state.step
        )
        new_state = TrainState(
            params=new_params,
            opt_state=new_opt,
            model_state=new_mstate,
            step=state.step + 1,
        )
        return new_state, {"loss": loss}

    return jax.jit(step, donate_argnums=(0,) if donate else ())
