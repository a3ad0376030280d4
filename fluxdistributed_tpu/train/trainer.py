"""Training orchestration — ``prepare_training`` + ``train``.

TPU-native re-design of the reference's orchestration layer
(src/ddp_tasks.jl:174-289).  Where the reference spawns one Julia task
per GPU, hub-reduces gradients on a HOST device and applies N replicated
optimizer steps, here ``prepare_training`` compiles ONE SPMD train step
over the mesh and ``train`` is a plain Python loop around it.  Feature
parity points, with their reference anchors:

* epoch→cycle accounting and per-shard loaders with prefetch
  (``prepare_training`` src/ddp_tasks.jl:249-289) → ``PrefetchLoader``;
* cycle print every 10 / eval every 50 with top-{1,5,10} accuracy on a
  val slice AND the current train batch
  (``train`` :185-191, ``log_loss_and_acc`` :128-148) → same cadences,
  configurable;
* LR-schedule callback kwarg (``sched`` :174,193-195 — unused identity
  in the reference) → schedules compile into the step via
  ``optim`` schedules; a per-cycle ``sched`` callback is still accepted
  and its value logged for parity;
* OOM fault tolerance: the reference catches device OOM and skips the
  batch with a (dead) ``num_missed`` counter (:230-238; counter declared
  :178, never incremented) → here the counter is live and returned;
* final host-side model return (:241-246) → ``train`` returns host
  copies of params/state.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import functools
import os
import sys
import time
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from .. import mesh as mesh_lib
from .. import sharding as sharding_lib
from .. import tree as tree_lib
from ..data.loader import PrefetchLoader
from ..obs import CompletionWatcher, Observation, get_tracer, jaxmon
from ..ops import logitcrossentropy
from ..optim import Optimizer
from ..parallel.dp import TrainState, flax_loss_fn, make_eval_step, make_train_step
from .guard import state_donated
from .logging import Logger, current_logger

__all__ = ["TrainTask", "evaluate", "prepare_training", "train"]


@dataclasses.dataclass
class TrainTask:
    """Everything ``train`` needs — the analog of the reference's
    ``(ds_and_ms, dls, sts), buffer`` bundle returned by
    ``prepare_training`` (src/ddp_tasks.jl:288), collapsed into one
    compiled step + one replicated state."""

    state: TrainState
    step_fn: Callable
    eval_fn: Callable
    loader: Iterable
    optimizer: Optimizer
    mesh: Mesh
    model: Any
    val_batch: Optional[dict] = None
    num_missed: int = 0
    # host-side batch hook (the loader's ``transform``), kept so
    # ``evaluate`` feeds the model the same layout training did
    transform: Optional[Callable] = None
    # optimizer steps per dispatch (the device loop); loader items carry
    # this many stacked batches and metrics come back stacked
    steps_per_call: int = 1
    # every global batch fed to step_fn/eval_fn must be a multiple of
    # this (0 = just the data-axis size).  Pipeline modes set it to
    # data_size x num_microbatches: the compiled schedule reshapes each
    # data shard into M microbatches, so eval/val batches must divide
    batch_quantum: int = 0
    # loader-item indices skipped by OOM fault tolerance, in order —
    # recorded so a resumed run can prove the cursor accounting (the
    # RESUME manifest carries them) and postmortems can name the lost
    # batches by global index
    skipped_items: list = dataclasses.field(default_factory=list)
    # loader-item indices quarantined by the anomaly guard (train/guard
    # .py) — restored from the RESUME manifest by resume_training so a
    # resumed/rolled-back run deterministically re-skips the same
    # batches (the loss-parity contract extends to guard decisions)
    quarantined_items: list = dataclasses.field(default_factory=list)
    # the top-k metrics compiled into eval_fn; ``train`` reports these
    # by default so a mode that compiles loss-only eval (the LM
    # pipelines) needs no caller-side coordination
    topk: tuple = (1, 5, 10)
    # the mesh axes the batch dim shards over — one name for the
    # classic modes, ("data", "fsdp") for the rule-derived 3-D layouts
    # (evaluate/shard paths must split batches over BOTH communicators)
    batch_axes: Any = mesh_lib.DATA_AXIS


def _eval_view(dataset):
    """A non-mutating eval view of ``dataset``: same tables/decoders,
    augmentation off.

    Eval draws must go through the eval pipeline even when the dataset
    augments its train split — but toggling ``dataset.augment`` in place
    (the old scheme) races a concurrent prefetch loader sharing the
    object, which would silently draw un-augmented TRAIN batches while
    an eval runs.  A shallow copy gives the eval path its own ``augment``
    flag while sharing the (read-only) sample tables underneath.
    """
    if getattr(dataset, "augment", False):
        view = copy.copy(dataset)
        view.augment = False
        return view
    return dataset


def _in_span(name: str, args: Callable[..., dict] = lambda *a, **kw: {}):
    """The whole of each call of the function as one span of the process
    tracer (``args`` makes the span's arguments from the call's), so
    every span the call opens names it as its parent."""
    def deco(fn):
        @functools.wraps(fn)
        def spanned(*a, **kw):
            with get_tracer().span(name, **args(*a, **kw)):
                return fn(*a, **kw)
        return spanned
    return deco


#: the spmd= names that were a second spelling of a layout, each with
#: the layout= that says the same
_RETIRED_SPMD = {
    "fsdp": 'layout="fsdp"',
    "tp": 'layout=Layout("tp", dp=D, tp=K)',
    "fsdp_tp": 'layout="fsdp_tp" (or Layout("fsdp_tp", fsdp=F, tp=K))',
}


@_in_span("prepare")
def prepare_training(
    model,
    dataset,
    optimizer: Optimizer,
    *,
    mesh: Optional[Mesh] = None,
    batch_size: int = 32,
    epochs: int = 1,
    cycles: Optional[int] = None,
    loss: Callable = logitcrossentropy,
    loss_fn: Optional[Callable] = None,
    val_dataset=None,
    val_samples: int = 300,
    buffersize: int = 5,
    seed: int = 0,
    input_shape: Optional[Sequence[int]] = None,
    spmd: str = "jit",
    zero1: bool = False,
    layout=None,
    donate: bool = False,
    topk: Sequence[int] = (1, 5, 10),
    accum_steps: int = 1,
    transform: Optional[Callable] = None,
    steps_per_call: int = 1,
    num_microbatches: Optional[int] = None,
    pipeline_interleave: bool = False,
    pipeline_schedule: str = "1f1b",
    pp_plan=None,
    cache_dir: Optional[str] = None,
    aot: Optional[str] = None,
    warmup: bool = False,
    strict_checks: bool = False,
    guard: bool = False,
) -> TrainTask:
    """Initialize params, compile the SPMD step, build prefetch loaders.

    Mirrors ``prepare_training(model, key, devices, opt, nsamples; ...)``
    (src/ddp_tasks.jl:249-289) with the device list replaced by a mesh and
    the per-device replication/buffers replaced by sharding annotations.

    ``val_samples`` defaults to the reference's 300-sample val slice
    (src/ddp_tasks.jl:145).  ``spmd`` selects the compiled program:
    ``"jit"`` (auto-sharded DP; ``"dp"`` is an alias), ``"shard_map"``
    (explicit collectives), ``"sp"``, ``"ep"``, ``"pp"``, ``"pp_1f1b"``.

    ``layout`` says where the state's tensors live: a
    :class:`~..parallel.layout.Layout` (or preset name: ``"fsdp"``,
    ``"tp"``, ``"fsdp_tp"``, ``"dp_fsdp"``, ...) on the dp x fsdp x tp
    grid.  The model family's rule table (``parallel/rules.py``) gives
    every leaf its spec and the step is the same ``dp.make_train_step``
    compiled with those shardings — same step math, ~N× lower state
    memory on an N-way fsdp axis.  It is the only way to shard state.

    ``zero1=True`` upgrades the DP paths (``"jit"``/``"dp"``/
    ``"shard_map"``) to ZeRO-1 weight-update sharding
    (``parallel/zero1.py``): gradients reduce-scatter, the optimizer
    state and update compute shard 1/N over the data axis, updated
    params all-gather — DP-identical numerics at ~N× lower optimizer
    memory.  Composes with ``accum_steps``, ``steps_per_call``,
    ``donate`` and OOM-skip; checkpoints carry the sharded optimizer
    state (orbax restores shard-to-shard).

    ``donate=True`` donates the TrainState buffers to each step (halves
    peak state memory — worthwhile for very large models) but is
    incompatible with OOM-skip: after a failed step the donated buffers
    are gone and training cannot continue (the loop raises a clear error
    instead of continuing).  Default False, matching the reference's
    skip-and-continue semantics (src/ddp_tasks.jl:230-238).

    ``loss_fn`` overrides the default image-classification adapter
    (``flax_loss_fn(model, loss)``) with any function matching the
    framework loss signature — e.g. ``models.lm_loss_fn(model)`` trains
    the transformer LM on a token dataset through this same path (pass
    ``topk=()``: top-k image metrics don't apply to LM batches).

    ``transform`` is the loader's host-side batch hook (per the dataset
    protocol: ``transform(imgs, labels)`` for tuple datasets, one
    argument otherwise) — e.g. ``models.space_to_depth`` re-layout for a
    ``space_to_depth=True`` ResNet.  It is applied consistently to the
    init sample, the train loader, the val slice, and ``evaluate``.

    ``steps_per_call > 1`` turns on the device loop: each loader item
    stacks K per-step batches and the compiled program ``lax.scan``s K
    optimizer steps per dispatch — identical math and identical sampled
    data (sub-batch j of item c equals step c·K+j of an unchunked run),
    but the host pays one dispatch per K steps.  Worthwhile when host
    dispatch latency is large or the host is slow; cadences
    in ``train`` (print/eval/checkpoint) then tick once per K steps.
    Supported for ``spmd='jit'``.

    Pipeline knobs (``spmd="pp"``/``"pp_1f1b"``): ``num_microbatches``
    sets M (default 2·S), ``pipeline_interleave`` the Megatron
    round-robin virtual stages, ``pipeline_schedule="zb"`` the
    zero-bubble B/W-split timetable (pp_1f1b only; bit-identical
    gradients, W work fills the drain), and ``pp_plan`` a
    :class:`~..parallel.pp_plan.PipelinePlan` (or saved-plan path)
    whose profile-guided non-uniform stage boundaries replace the
    uniform block split — cross-topology plans are rejected through
    the profile fingerprint check, and a plan lifts the
    ``depth % S == 0`` requirement.

    Cold-start controls (:mod:`fluxdistributed_tpu.compilation`):

    * ``cache_dir`` enables JAX's persistent compilation cache BEFORE
      any compile in this call, so the next process on the same
      topology reads every XLA compile from disk instead of redoing it.
      The directory follows ``compilation.resolve_cache_dir``: where
      ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives exactly
      there (and is enabled whatever ``cache_dir`` says), else at
      ``cache_dir``.
    * ``aot`` names a directory of serialized train-step executables:
      the compiled step is loaded from disk when a file matching this
      topology + argument signature exists, else compiled NOW (at
      prepare time, not at first step) and serialized for the next
      process.  Unlike the persistent cache, a serialized executable
      also skips tracing and lowering.  Requires a jit-compiled step
      (every current spmd mode qualifies).
    * ``warmup=True`` runs one optimizer step on donated zero-filled
      dummies (the returned task's real state is untouched) before
      returning, so the first ``train`` step — and anything timing it —
      starts warm.

    ``guard=True`` compiles the anomaly sentinel into the train step
    (``parallel.dp.guard_sentinel``: ``metrics["guard"] =
    [poisoned_loss, grad_norm]``, the global isfinite any-reduce over
    loss + grads plus the global grad norm, in-graph where the
    gradients already live) so ``train(guard=GuardConfig(...))`` can
    detect bad steps at ONE extra scalar fetch per step and zero extra
    compiles.  Supported on the paths that ride
    ``dp.make_train_step`` — ``jit``/``dp`` (with or without
    ``zero1``, under any ``layout``), ``sp``, ``ep`` and the GPipe
    ``pp`` — and requires
    ``donate=False``: recovery re-uses the pre-step state, exactly like
    OOM-skip.  Other modes still run the guard loss-only (non-finite
    loss + spikes) without this flag.

    ``strict_checks=True`` arms the returned step/eval functions for
    their first TWO invocations: call 1 runs with ``jax_debug_nans`` on
    (a NaN/Inf in the outputs raises and jax re-runs op-by-op to name
    the producing primitive), call 2 under
    ``jax.transfer_guard("disallow")`` (any implicit host↔device
    transfer raises — the hazard the lint suite's FDT205 check hunts;
    the guard sits on the steady-state call because step-0 one-time
    commits are legitimate).  Failures raise with an actionable message
    naming the offending phase ("first train step" / "steady-state
    eval step"); subsequent calls run at full speed with both checks
    off.  Debug-grade: the armed calls also block until the device
    finishes.
    """
    from ..data.loader import apply_transform

    from .. import compilation

    # the set-up on the program's own timeline: the whole call is a
    # ``prepare`` span, and what follows opens its children where the
    # work happens (a phase that does not run leaves no span)
    span = get_tracer().span
    jaxmon.install()  # every compile, trace and lowering below is a span
    if cache_dir or os.environ.get(compilation.CACHE_DIR_ENV):
        with span("cache_enable"):
            compilation.enable_persistent_cache(cache_dir)

    if spmd == "dp":  # explicit-name alias for the auto-sharded DP path
        spmd = "jit"
    if spmd in _RETIRED_SPMD:
        raise ValueError(
            f"spmd={spmd!r} is gone: where tensors live is said by "
            f"layout=, e.g. {_RETIRED_SPMD[spmd]} (rule tables in "
            "parallel/rules.py, the grid in parallel/layout.py)")
    if layout is not None:
        # the declarative path (parallel/rules.py + parallel/layout.py):
        # a dp×fsdp×tp Layout (or preset name) whose rule-derived spec
        # tree drives the UNCHANGED dp step — it subsumes the modes it
        # composes, so combining it with one of them is a contradiction
        from ..parallel import layout as layout_lib

        if spmd != "jit":
            raise ValueError(
                f"layout= builds the rule-derived 3-D step and cannot "
                f"combine with spmd={spmd!r} (keep the default "
                "spmd='jit'/'dp')")
        if zero1:
            raise ValueError(
                "layout= cannot combine with zero1=True: a layout's "
                "fsdp axis already shards the optimizer state "
                "(ZeRO-3 placement subsumes ZeRO-1) — use e.g. "
                "layout='fsdp' or 'dp_fsdp'")
        if steps_per_call != 1:
            raise ValueError("steps_per_call > 1 is not supported with "
                             "layout= (yet) — drop one of them")
        # a caller-supplied mesh defines the topology (it may span a
        # device SUBSET — build_mesh(devs=...) is supported surface);
        # validate_mesh below still pins the axis sizes exactly
        layout = layout_lib.resolve_layout(
            layout,
            ndev=int(mesh.devices.size) if mesh is not None else None)
    if steps_per_call < 1:
        raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
    if steps_per_call != 1 and spmd != "jit":
        raise ValueError("steps_per_call > 1 requires spmd='jit'")
    if zero1 and spmd not in ("jit", "shard_map"):
        raise ValueError(
            "zero1=True applies to the DP paths only (spmd='jit'/'dp'/"
            f"'shard_map'); got spmd={spmd!r}"
        )
    if guard:
        if donate:
            raise ValueError(
                "guard=True requires donate=False: anomaly recovery "
                "discards the poisoned step and continues from the "
                "PRE-step state, which donation would have freed "
                "(the same contract as OOM-skip)")
        if spmd not in ("jit", "sp", "ep", "pp"):
            raise ValueError(
                f"guard=True compiles the grad sentinel into "
                f"dp.make_train_step, which spmd={spmd!r} does not use "
                "(supported: jit/dp [+zero1], sp, ep, pp) — the guard "
                "still runs loss-only there: drop guard=True and pass "
                "train(guard=GuardConfig(...))")
    if num_microbatches is not None and spmd not in ("pp", "pp_1f1b"):
        raise ValueError("num_microbatches requires spmd='pp' or 'pp_1f1b'")
    if num_microbatches is not None and num_microbatches < 1:
        # validated HERE with the other argument checks, before any
        # pipeline-specific model wiring, so the error fires identically
        # across spmd modes and model types
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}")
    if pipeline_interleave and spmd != "pp_1f1b":
        raise ValueError(
            "pipeline_interleave requires spmd='pp_1f1b' (the hand-written "
            "schedule; GPipe-via-AD cannot interleave)")
    if pipeline_schedule not in ("1f1b", "zb"):
        raise ValueError(
            f"unknown pipeline_schedule {pipeline_schedule!r} "
            "(pick '1f1b' or 'zb')")
    if pipeline_schedule != "1f1b" and spmd != "pp_1f1b":
        raise ValueError(
            "pipeline_schedule='zb' requires spmd='pp_1f1b' (the zero-"
            "bubble B/W split only exists in the hand-written schedule)")
    if pp_plan is not None and spmd not in ("pp", "pp_1f1b"):
        raise ValueError("pp_plan requires spmd='pp' or 'pp_1f1b'")
    if pp_plan is not None and pipeline_interleave:
        raise ValueError(
            "pp_plan cannot combine with pipeline_interleave: planner "
            "boundaries are contiguous block ranges, the interleaved "
            "placement is round-robin")
    if layout is not None:
        mesh = mesh or layout.build_mesh()
        layout.validate_mesh(mesh)
    else:
        mesh = mesh or mesh_lib.data_mesh()
    init_draw = None
    # a data-axis-divisible init sample for the modes whose models
    # contain a mesh-bound shard_map (ring attention, MoE dispatch) —
    # those execute it during init, and a batch of 1 cannot shard over
    # a >1 data axis.  Other modes keep the cheap single-sample init.
    ninit = mesh.shape.get(mesh_lib.DATA_AXIS, 1) if spmd in ("sp", "ep") else 1
    with span("model_init"):
        if input_shape is not None:
            dummy = np.zeros((ninit, *input_shape), np.float32)
        else:
            # draw real samples so init sees the dataset's true shape AND
            # dtype (f32 images, int32 tokens, ...); kept for the pp_1f1b
            # mask probe below so startup draws only once
            from ..data.loader import model_input

            init_draw = apply_transform(
                transform, dataset.batch(np.random.default_rng(0), ninit))
            dummy = model_input(init_draw)

        p_rng, d_rng = jax.random.split(jax.random.PRNGKey(seed))
        # 'dropout' stream present at init so stochastic models (ViT
        # dropout, ConvNeXt drop-path) initialize under train=True
        variables = model.init(
            {"params": p_rng, "dropout": d_rng}, dummy, train=True)
    params = variables["params"]
    model_state = {k: v for k, v in variables.items() if k != "params"}  # e.g. batch_stats

    custom_loss_fn = loss_fn is not None
    if loss_fn is None:
        loss_fn = flax_loss_fn(model, loss)
    batch_quantum = 0  # pipeline modes raise it to data_size x microbatches
    batch_axes = mesh_lib.DATA_AXIS  # layouts widen it to (data, fsdp)
    if layout is not None:
        # declarative rule-derived sharding (ROADMAP item 3): the model
        # family's committed rule table + the fsdp overlay produce the
        # spec tree; the step itself is the UNCHANGED dp step compiled
        # with those shardings and the batch split over (data, fsdp) —
        # GSPMD derives the dp/ZeRO-3/Megatron collective composition
        # from the annotations
        from ..parallel import layout as layout_lib

        with span("model_init"):
            state, sh = layout_lib.shard_state(
                model,
                TrainState.create(params, optimizer, model_state=model_state),
                layout, mesh)
        batch_axes = layout.batch_axes
        if batch_size % layout.batch_shards:
            raise ValueError(
                f"batch_size {batch_size} must be divisible by the "
                f"layout's dp x fsdp = {layout.batch_shards} "
                f"({layout.describe()})")
        batch_quantum = layout.batch_shards
        with span("step_build"):
            step_fn = make_train_step(
                loss_fn, optimizer, mesh, axis=batch_axes,
                donate=donate, accum_steps=accum_steps, seed=seed,
                state_shardings=sh, guard=guard)
            eval_fn = make_eval_step(
                loss_fn, mesh, axis=batch_axes, topk=tuple(topk),
                state_shardings=sh)
    elif spmd in ("pp", "pp_1f1b"):
        # Pipeline-parallel LM training as a first-class trainer mode:
        # decoder blocks stage-sharded over a 'pipe' axis, composed with
        # data parallelism over the 'data' axis (size 1 is fine — build
        # the mesh as make_mesh({"data": D, "pipe": S})).  "pp" rides
        # the GPipe schedule through the generic jit step; "pp_1f1b"
        # compiles the hand-scheduled 1F1B train step (O(S) activation
        # memory) and still evaluates through the GPipe forward — both
        # schedules share the same split param tree and shardings.
        from ..models.transformer_lm import TransformerLM, lm_pp, lm_pp_1f1b
        from ..parallel.pp_1f1b import make_train_step_1f1b

        if not isinstance(model, TransformerLM):
            raise ValueError(
                f"spmd={spmd!r} supports TransformerLM only (CNN stages "
                "change activation shapes mid-network)"
            )
        if accum_steps != 1:
            raise ValueError("accum_steps > 1 requires spmd='jit'")
        if custom_loss_fn:
            raise ValueError(
                f"spmd={spmd!r} trains on the pipeline's own per-microbatch "
                "next-token loss; a loss_fn override cannot apply (drop it)"
            )
        # top-k image metrics can never apply to the LM pipeline; the
        # compiled eval returns loss only
        topk = ()
        for ax in (mesh_lib.PIPE_AXIS, mesh_lib.DATA_AXIS):
            if ax not in mesh.shape:
                raise ValueError(
                    f"spmd={spmd!r} needs a mesh with 'data' and 'pipe' "
                    "axes, e.g. make_mesh({'data': 1, 'pipe': 8})"
                )
        if model_state:
            raise ValueError(
                f"spmd={spmd!r} supports stateless models only "
                f"(got model_state collections {list(model_state)})"
            )
        if spmd == "pp_1f1b":
            # the 1F1B step's per-microbatch loss reads tokens only; a
            # mask-carrying dataset would train unmasked while eval (the
            # GPipe forward) applies the mask — reject the divergence
            from ..data.loader import batch_to_dict

            draw = init_draw if init_draw is not None else apply_transform(
                transform, dataset.batch(np.random.default_rng(0), 1))
            probe = batch_to_dict(draw, getattr(dataset, "nclasses", None))
            if "mask" in probe:
                raise ValueError(
                    "spmd='pp_1f1b' does not support batch['mask'] (the "
                    "1F1B per-microbatch loss reads tokens only) — use "
                    "spmd='pp', whose loss applies the mask"
                )
        S = mesh.shape[mesh_lib.PIPE_AXIS]
        n_data = mesh.shape[mesh_lib.DATA_AXIS]
        M = num_microbatches or 2 * S
        # planner boundaries: accept a PipelinePlan or a saved plan
        # artifact path; reject cross-topology plans (profile-derived
        # fingerprints) and plans for a different stack/axis
        boundaries = None
        if pp_plan is not None:
            from ..parallel.pp_plan import PipelinePlan

            if isinstance(pp_plan, str):
                pp_plan = PipelinePlan.load(pp_plan)
            pp_plan.verify_source_topology()
            if pp_plan.S != S:
                raise ValueError(
                    f"pp_plan places {pp_plan.S} stages but the "
                    f"'{mesh_lib.PIPE_AXIS}' axis has {S} — re-plan for "
                    "this mesh")
            if pp_plan.depth != model.depth:
                raise ValueError(
                    f"pp_plan partitions {pp_plan.depth} blocks but the "
                    f"model has depth {model.depth} — re-plan for this "
                    "model")
            boundaries = pp_plan.boundaries
        per_row = batch_size // n_data
        if batch_size % n_data or per_row % M:
            raise ValueError(
                f"batch_size {batch_size} must split into data axis "
                f"{n_data} x microbatches {M} (per-row batch {per_row})"
            )
        batch_quantum = n_data * M

        if pipeline_interleave:
            # interleaved placement's round-robin param layout cannot
            # feed the (blocked) GPipe forward, so BOTH the train step
            # and eval ride the 1F1B program (eval returns its loss and
            # discards the grads — ~3x a forward, fine for val slices)
            from ..parallel.pp_1f1b import pipeline_grads_1f1b
            from jax.sharding import NamedSharding, PartitionSpec as P

            w = lm_pp_1f1b(model, mesh, interleave=True)
            with span("model_init"):
                state = TrainState.create(w.split_params(params), optimizer)
                sh = w.state_shardings(state)
                state = jax.tree.map(jax.device_put, state, sh)
            with span("step_build"):
                step_fn = make_train_step_1f1b(
                    *w.fns, optimizer, mesh, num_microbatches=M,
                    batch_axis=mesh_lib.DATA_AXIS, interleave=w.interleave,
                    donate=donate, schedule=pipeline_schedule,
                )(state)
                eval_run = pipeline_grads_1f1b(
                    *w.fns, mesh, num_microbatches=M,
                    batch_axis=mesh_lib.DATA_AXIS, interleave=w.interleave,
                )

                def _eval(state, batch):
                    loss, _, _ = eval_run(
                        state.params["stages"], state.params["outer"],
                        batch["tokens"], batch["tokens"],
                    )
                    return loss, {}

                eval_fn = jax.jit(
                    _eval,
                    in_shardings=(sh,
                                  NamedSharding(mesh, P(mesh_lib.DATA_AXIS))),
                )
        else:
            split_params, pp_loss_fn, shardings_fn = lm_pp(
                model, mesh, batch_axis=mesh_lib.DATA_AXIS,
                num_microbatches=M, boundaries=boundaries,
            )
            with span("model_init"):
                state = TrainState.create(split_params(params), optimizer)
                sh = shardings_fn(state)
                state = jax.tree.map(jax.device_put, state, sh)
            with span("step_build"):
                if spmd == "pp":
                    step_fn = make_train_step(
                        pp_loss_fn, optimizer, mesh, axis=mesh_lib.DATA_AXIS,
                        donate=donate, state_shardings=sh, guard=guard,
                    )
                else:
                    w = lm_pp_1f1b(model, mesh, boundaries=boundaries)
                    step_fn = make_train_step_1f1b(
                        *w.fns, optimizer, mesh, num_microbatches=M,
                        batch_axis=mesh_lib.DATA_AXIS, interleave=w.interleave,
                        donate=donate, schedule=pipeline_schedule,
                    )(state)
                # eval through the GPipe forward: same tree, same shardings
                eval_fn = make_eval_step(
                    pp_loss_fn, mesh, topk=tuple(topk), state_shardings=sh
                )
    elif spmd == "ep":
        # MoE expert parallelism as a trainer mode: expert-stacked
        # leaves shard over the 'expert' axis, tokens ride the 'data'
        # axis, and the model's mesh-bound moe_fn (moe_apply) does the
        # all_to_all dispatch inside the generic jit step.  The model
        # must have been CONSTRUCTED with that moe_fn — it closes over
        # the mesh (bin/driver.py builds it from --spmd ep flags).
        from ..models.transformer_lm import TransformerLM, lm_loss_fn, lm_moe_specs
        from ..parallel.rules import train_state_specs
        from ..sharding import make_shardings

        if not isinstance(model, TransformerLM) or not model.moe_every:
            raise ValueError(
                "spmd='ep' needs a TransformerLM with moe_every > 0 and a "
                "mesh-bound moe_fn (models.moe_expert_fn via ep.moe_apply)"
            )
        if accum_steps != 1:
            raise ValueError("accum_steps > 1 requires spmd='jit'")
        for ax in (mesh_lib.EXPERT_AXIS, mesh_lib.DATA_AXIS):
            if ax not in mesh.shape:
                raise ValueError(
                    "spmd='ep' needs a mesh with 'data' and 'expert' axes, "
                    "e.g. make_mesh({'data': 1, 'expert': 8})"
                )
        if not custom_loss_fn:
            loss_fn = lm_loss_fn(model)  # token protocol, not image loss
        topk = ()  # image metrics can never apply to the LM
        with span("model_init"):
            state = TrainState.create(
                params, optimizer, model_state=model_state)
            sh = make_shardings(
                train_state_specs(state, lm_moe_specs(params)), mesh)
            state = jax.tree.map(jax.device_put, state, sh)
        with span("step_build"):
            step_fn = make_train_step(
                loss_fn, optimizer, mesh, axis=mesh_lib.DATA_AXIS,
                donate=donate, seed=seed, state_shardings=sh, guard=guard,
            )
            eval_fn = make_eval_step(
                loss_fn, mesh, topk=(), state_shardings=sh)
    else:
        if spmd not in ("jit", "shard_map", "sp"):
            raise ValueError(
                f"unknown spmd mode {spmd!r}; pick one of jit (alias dp) / "
                "shard_map / pp / pp_1f1b / ep / sp (layout= shards state)"
            )
        if spmd == "sp":
            # sequence/context parallelism rides the plain jit path with
            # REPLICATED params: the model's mesh-bound attn_fn (ring /
            # Ulysses, parallel/context.py) shards the sequence dim over
            # the 'seq' axis inside its own shard_map, and the batch
            # stays data-sharded.  Only the mesh shape needs checking.
            for ax in (mesh_lib.SEQ_AXIS, mesh_lib.DATA_AXIS):
                if ax not in mesh.shape:
                    raise ValueError(
                        "spmd='sp' needs a mesh with 'data' and 'seq' axes, "
                        "e.g. make_mesh({'data': 1, 'seq': 8}), and a model "
                        "built with attn_fn=make_ring_attention(mesh, "
                        "batch_axis='data', ...)"
                    )
        if spmd == "shard_map" and accum_steps != 1:
            raise ValueError("accum_steps > 1 requires spmd='jit'")
        if zero1:
            # ZeRO-1: DP step math, optimizer state + update sharded 1/N
            # over the data axis (parallel/zero1.py)
            from ..parallel import zero1 as zero1_lib

            with span("model_init"):
                state, z_sh = zero1_lib.zero1_state(
                    params, optimizer, mesh, model_state=model_state
                )
            with span("step_build"):
                if spmd == "shard_map":
                    step_fn = zero1_lib.make_train_step_zero1_shardmap(
                        loss_fn, optimizer, mesh, state,
                        donate=donate, seed=seed
                    )
                else:
                    step_fn = zero1_lib.make_train_step_zero1(
                        loss_fn, optimizer, mesh, z_sh,
                        donate=donate, accum_steps=accum_steps, seed=seed,
                        steps_per_call=steps_per_call, guard=guard,
                    )
                eval_fn = make_eval_step(
                    loss_fn, mesh, topk=tuple(topk), state_shardings=z_sh
                )
        else:
            with span("step_build"):
                if spmd == "shard_map":
                    from ..parallel.dp import (
                        make_train_step_shardmap as maker)

                    step_fn = maker(
                        loss_fn, optimizer, mesh, donate=donate, seed=seed)
                else:
                    step_fn = make_train_step(
                        loss_fn, optimizer, mesh,
                        donate=donate, accum_steps=accum_steps, seed=seed,
                        steps_per_call=steps_per_call, guard=guard,
                    )
                eval_fn = make_eval_step(loss_fn, mesh, topk=tuple(topk))

            with span("model_init"):
                state = TrainState.create(
                    sharding_lib.replicate(params, mesh),
                    optimizer,
                    model_state=sharding_lib.replicate(model_state, mesh),
                )

    with span("step_build"):
        loader = PrefetchLoader(
            dataset,
            mesh,
            batch_size,
            cycles=cycles,
            epochs=epochs,
            buffersize=buffersize,
            seed=seed,
            axis=batch_axes,
            transform=transform,
            chunk=steps_per_call,
        )

        val_batch = None
        if val_dataset is not None:
            # divisible val slice: a data-axis multiple, and for pipeline
            # modes a multiple of data_size x microbatches (the compiled
            # eval reshapes each data shard into M microbatches)
            q = batch_quantum or mesh.shape[mesh_lib.DATA_AXIS]
            nval = max(q, (val_samples // q) * q)
            # Validation must go through the eval pipeline even when the
            # val dataset was carved from an augmenting train table.
            vdraw = apply_transform(
                transform,
                _eval_view(val_dataset).batch(
                    np.random.default_rng(seed + 1), nval),
            )
            from ..data.loader import batch_to_dict

            val_batch = sharding_lib.shard_batch(
                batch_to_dict(vdraw, getattr(val_dataset, "nclasses", None)),
                mesh, axis=batch_axes,
            )

    task = TrainTask(
        state=state,
        step_fn=step_fn,
        eval_fn=eval_fn,
        loader=loader,
        optimizer=optimizer,
        mesh=mesh,
        model=model,
        val_batch=val_batch,
        transform=transform,
        steps_per_call=steps_per_call,
        batch_quantum=batch_quantum,
        topk=tuple(topk),
        batch_axes=batch_axes,
    )
    # a handful of state leaves (the step counter; any scalar the
    # optimizer creates from literals) are born uncommitted on one
    # device, while the step RETURNS them committed to the replicated
    # sharding: left alone, the second step call sees a new input
    # signature and compiles the whole train step a second time
    with span("model_init"):
        task.state = _commit_replicated_stragglers(task.state, mesh)

    if aot or warmup:
        # the batch both compile against counts with the first to use it
        with span("aot" if aot else "warmup"):
            dummy = _dummy_batch(
                dataset, transform, batch_size, mesh, steps_per_call, seed,
                axis=batch_axes)
        if aot:
            # the tag covers everything that changes the compiled
            # program WITHOUT changing argument shapes: mode/schedule
            # knobs, model hyperparameters like attention windows, and
            # the optimizer/loss with their closed-over hyperparameters
            # (a different learning rate bakes different constants into
            # the same-shaped program — config_tag digests callables by
            # name + closure constants, address-free).  Argument
            # shapes/shardings are the signature's job inside
            # load_or_compile
            # "guard" appended only when on: the sentinel adds outputs
            # to the compiled program, so a guarded step must never
            # load an unguarded executable (or vice versa) — while
            # guard-off runs keep their pre-existing tags byte-for-byte
            # pipeline_schedule and the plan's boundaries both change
            # the compiled program at identical argument shapes (zb
            # adds W ticks + the cot stash; a plan re-pads the chunk
            # scan), so they must split the AOT key — appended only
            # when NON-default, so every pre-existing run keeps its
            # tag byte-for-byte (same contract as the guard flag: a
            # warm executable pool must survive this upgrade)
            tag = compilation.config_tag(
                spmd, zero1, accum_steps, steps_per_call, donate, seed,
                num_microbatches, pipeline_interleave, repr(model),
                optimizer.name, optimizer.update, loss_fn, loss,
                *(("guard",) if guard else ()),
                # a layout changes the compiled program (shardings) at
                # identical shapes; appended only when set so every
                # pre-existing run keeps its tag byte-for-byte
                *((f"layout:{layout.name}:{sorted(layout.sizes.items())}",)
                  if layout is not None else ()),
                *((pipeline_schedule,) if pipeline_schedule != "1f1b"
                  else ()),
                # a UNIFORM plan builds the no-plan program exactly, so
                # it must also share the no-plan AOT key
                *((repr(pp_plan.boundaries),)
                  if pp_plan is not None and not pp_plan.is_uniform
                  else ()))
            task.step_fn = compilation.load_or_compile(
                task.step_fn, (task.state, dummy),
                directory=aot, name="train_step",
                fingerprint=compilation.topology_fingerprint(
                    mesh=mesh, tag=tag),
            )
            # an AOT executable (unlike jit) does NOT reshard inputs:
            # commit the state to the exact shardings it was compiled
            # with (no-op transfers for already-matching leaves; the
            # step's output shardings keep the loop consistent after)
            in_sh = getattr(task.step_fn, "input_shardings", None)
            if in_sh is not None:
                task.state = jax.tree.map(
                    jax.device_put, task.state, in_sh[0][0])
        if warmup:
            stats = compilation.warmup_train(task, dummy)
            current_logger().info(
                f"warmup: {int(stats['compiles'])} compiles "
                f"({stats['compile_seconds']:.1f}s of "
                f"{stats['seconds']:.1f}s) pre-paid before step 0")

    if strict_checks:
        task.step_fn = _strict_first_call(task.step_fn, "train step")
        task.eval_fn = _strict_first_call(task.eval_fn, "eval step")

    return task


def _commit_replicated_stragglers(state, mesh: Mesh):
    """Commit any single-device state leaf to the replicated sharding on
    ``mesh``.  Mode-specific prepare paths device_put their whole state;
    the plain DP paths leave computation-born scalars (``state.step``)
    uncommitted.  Holds on a one-device mesh too: there the step still
    returns ``NamedSharding`` leaves, a different jit signature from the
    ``SingleDeviceSharding`` they were born with."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    repl = NamedSharding(mesh, PartitionSpec())

    def fix(x):
        if isinstance(x, jax.Array) and isinstance(x.sharding, SingleDeviceSharding):
            return jax.device_put(x, repl)
        return x

    return jax.tree.map(fix, state)


def _strict_first_call(fn, phase: str):
    """``strict_checks`` wrapper: call 1 runs under ``jax_debug_nans``
    (a NaN/Inf raises and jax re-runs op-by-op to name the producing
    primitive), call 2 under ``jax.transfer_guard("disallow")`` (any
    implicit host↔device transfer raises); later calls pass straight
    through.  The two checks must not share a call: debug-nans' op-by-op
    re-run itself performs host transfers, so a guard around it would
    mask the NaN diagnosis with a transfer error.  Putting the guard on
    call 2 is also the honest check — step-0 one-time commits are
    legitimate, a transfer on call 2 recurs every step (same protocol as
    the lint suite's FDT205).  (The wrapper hides a jit object's
    ``.lower`` — AOT-export a task before arming it with strict
    checks.)"""
    stage = {"n": 0}

    def wrapped(*args, **kwargs):
        n = stage["n"]
        if n >= 2:
            return fn(*args, **kwargs)
        stage["n"] = n + 1
        if n == 0:
            old_nans = bool(jax.config.jax_debug_nans)
            jax.config.update("jax_debug_nans", True)
            try:
                out = fn(*args, **kwargs)
                # surface device-side NaN checks inside the debug
                # window, not at some later sync point
                jax.block_until_ready(jax.tree.leaves(out))
            except FloatingPointError as e:
                raise FloatingPointError(
                    f"strict_checks: NaN/Inf produced by the first "
                    f"{phase} — jax_debug_nans re-ran it op-by-op above "
                    "to name the producing primitive; check the input "
                    "batch, init scales and the learning rate"
                ) from e
            finally:
                jax.config.update("jax_debug_nans", old_nans)
            return out
        try:
            with jax.transfer_guard("disallow"):
                out = fn(*args, **kwargs)
                jax.block_until_ready(jax.tree.leaves(out))
        except Exception as e:
            msg = str(e)
            if "transfer" in msg.lower():
                raise RuntimeError(
                    f"strict_checks: implicit host<->device transfer "
                    f"during the steady-state {phase}: {msg[:300]} — "
                    "commit inputs up front (sharding.shard_batch for "
                    "batches, jax.device_put for state); a transfer here "
                    "recurs on EVERY step and serializes the dispatch "
                    "pipeline"
                ) from e
            raise
        return out

    return wrapped


def _dummy_batch(dataset, transform, batch_size, mesh, steps_per_call, seed,
                 axis=mesh_lib.DATA_AXIS):
    """One batch with training's exact layout (transform applied,
    device-sharded, stacked when the device loop is on) for AOT
    lowering and warmup — drawn from the dataset so shapes AND dtypes
    are the real ones, discarded after use."""
    from ..data.loader import apply_transform, batch_to_dict

    draw = apply_transform(
        transform, dataset.batch(np.random.default_rng(seed + 2), batch_size))
    bd = batch_to_dict(draw, getattr(dataset, "nclasses", None))
    if steps_per_call > 1:
        # the loader's chunk layout: K stacked per-step batches sharded
        # P(None, data) — leading dim is the scan axis, not the batch.
        # Routed through the canonical local-rows→global-array boundary
        # (batch_dim=1, like the loader) so multi-process warmup works
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.multihost import global_batch_put, local_batch_size

        s = NamedSharding(mesh, PartitionSpec(None, mesh_lib.DATA_AXIS))
        pi = jax.process_index()

        def put(v):
            rows = local_batch_size(v.shape[0])
            local = np.asarray(v[pi * rows:(pi + 1) * rows])
            return global_batch_put(
                np.stack([local] * steps_per_call), s, batch_dim=1)

        return {k: put(v) for k, v in bd.items()}
    return sharding_lib.shard_batch(bd, mesh, axis=axis)


def restore_training(
    task: TrainTask, checkpoint_dir: str, step: Optional[int] = None
) -> TrainTask:
    """Resume a prepared task from a checkpoint — the path the reference
    lacks entirely (SURVEY §5: "no resume"; its checkpoints hold model
    weights only, src/sync.jl:156-161, while ours carry params +
    optimizer state + BatchNorm stats + step counter).

    Restores the latest (or given) step from ``checkpoint_dir`` onto the
    task's mesh, replicated, ready for ``train``.  For preemption-aware
    resume (data-loader cursor + elastic device-count change) use
    :func:`resume_training`.
    """
    from .checkpoint import load_checkpoint

    task.state = load_checkpoint(checkpoint_dir, task.state, step=step, mesh=task.mesh)
    return task


def resume_training(
    task: TrainTask, checkpoint_dir: str, step: Optional[int] = None
) -> Optional[dict]:
    """Preemption-aware resume: restore state AND the run cursor so a
    resumed run is step-for-step identical to an uninterrupted one.

    Reads the RESUME manifest a preempted ``train`` left next to its
    checkpoint (step, data-loader cursor, skipped items, mesh
    topology).  When the manifest's topology matches the task's, the
    checkpoint restores sharded in place; on a device-count change
    (the elastic case — the next grant gave a different slice) it
    restores via host arrays and re-commits every leaf to the NEW
    mesh's shardings, re-splitting ZeRO-1's padded flat optimizer
    shards (:func:`..train.checkpoint.load_checkpoint_elastic`).

    Returns the manifest (or ``None``: no manifest — plain
    latest-checkpoint resume with the cursor derived from the step
    counter; or nothing on disk at all — the task is left untouched,
    a fresh run).
    """
    from .. import faults
    from .checkpoint import (
        latest_step, load_checkpoint, load_checkpoint_elastic,
        read_resume_manifest,
    )

    faults.fire("resume")
    manifest = read_resume_manifest(checkpoint_dir)
    ckpt_step = (manifest or {}).get("checkpoint_step", step)
    if ckpt_step is None:
        ckpt_step = latest_step(checkpoint_dir)
        if ckpt_step is None:
            return None  # nothing saved yet: fresh run
    mesh_now = {k: int(v) for k, v in dict(task.mesh.shape).items()}
    same_topology = manifest is None or (
        manifest.get("device_count") == jax.device_count()
        and manifest.get("mesh") == mesh_now
    )
    if same_topology:
        task.state = load_checkpoint(
            checkpoint_dir, task.state, step=ckpt_step, mesh=task.mesh)
    else:
        task.state = load_checkpoint_elastic(
            checkpoint_dir, task.state, step=ckpt_step)
    spc = max(1, getattr(task, "steps_per_call", 1))
    if manifest is not None:
        task.loader.start = int(manifest.get("next_item", 0))
        task.num_missed = int(manifest.get("num_missed", 0))
        task.skipped_items = list(manifest.get("skipped_items", []))
        # guard decisions survive the process: a resumed run re-skips
        # the quarantined batches (train() seeds its TrainGuard here)
        task.quarantined_items = [
            int(x) for x in manifest.get("quarantined_items", [])]
    else:
        # no manifest (a cadence checkpoint from an old-style run):
        # the step counter is the only cursor — correct when nothing
        # was OOM-skipped before the checkpoint
        task.loader.start = int(task.state.step) // spc
    return manifest


def _is_oom(err: Exception) -> bool:
    s = str(err)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s or "OOM" in s


def _require_topk(accs: dict, topk) -> None:
    """Fail fast when a requested top-k metric was never compiled into
    the eval step (shared by the train-loop eval and evaluate())."""
    for k in topk:
        if f"top{k}" not in accs:
            raise KeyError(
                f"top-{k} accuracy was not compiled into the eval step — pass "
                f"topk={tuple(topk)} to prepare_training"
            )


def _eval_and_log(task: TrainTask, batch, name: str, step: int, topk, logger: Logger):
    """Loss + top-k accuracy on one batch — ``log_loss_and_acc``
    (src/ddp_tasks.jl:128-148), computed entirely in the compiled eval
    step (replicated scalar outputs, multi-host safe)."""
    loss, accs = task.eval_fn(task.state, batch)
    _require_topk(accs, topk)
    metrics = {f"{name}_loss": float(loss)}
    for k in topk:
        metrics[f"{name}_top{k}"] = float(accs[f"top{k}"])
    logger.log(metrics, step)
    return metrics


def evaluate(
    task: TrainTask,
    dataset,
    *,
    batch_size: int = 256,
    max_batches: Optional[int] = None,
    topk: Optional[Sequence[int]] = None,
    seed: int = 0,
) -> dict:
    """Aggregate loss/top-k over a dataset with the compiled eval step —
    beyond the reference, which only ever evals a fixed 300-sample slice
    (src/ddp_tasks.jl:145).

    Coverage semantics: when the dataset supports explicit ``indices``
    and has a length, every sample is drawn EXACTLY once via sequential
    index blocks; a trailing remainder runs as one extra smaller batch
    (its own compile — shapes are static), so at most ``quantum - 1``
    samples are ever dropped, where ``quantum`` is the task's batch
    granularity: the data-axis size for most modes, raised to
    ``data_size × num_microbatches`` for pipeline tasks (whose compiled
    eval reshapes each data shard into M microbatches).  Otherwise —
    generated token streams etc. — batches are sampled and
    ``max_batches`` is required (the result is then a stochastic
    estimate, flagged by ``"exact": False``).

    Returns sample-weighted means ``{"loss": ..., "top1": ..., ...}``
    plus ``"samples"``, ``"exact"``, and (on the exact path) ``"dropped"``
    — the < quantum unreachable leftovers.  Requested top-k metrics must
    have been compiled into the eval step (``prepare_training(topk=...)``).
    """
    import inspect

    from ..data.loader import apply_transform, batch_to_dict

    if topk is None:
        # report exactly the metrics compiled into the task's eval step
        # (loss-only for the LM pipeline modes) — same default as train()
        topk = getattr(task, "topk", (1, 5, 10))

    capable = (
        hasattr(dataset, "__len__")
        and "indices" in inspect.signature(dataset.batch).parameters
    )
    n_axis = task.mesh.shape.get(mesh_lib.DATA_AXIS, 1)
    # the granularity every fed batch must divide into: the data axis,
    # raised to data_size x microbatches for pipeline tasks (their
    # compiled eval reshapes each data shard into M microbatches)
    quantum = task.batch_quantum or n_axis
    requested = batch_size
    if capable:
        # batch must stay shardable on the data axis AND inside the
        # dataset; shrink it for small datasets instead of indexing past
        # the end
        max_bs = len(dataset) // quantum * quantum
        if max_bs == 0:
            raise ValueError(
                f"dataset has {len(dataset)} samples — fewer than the "
                f"batch granularity {quantum} (data axis {n_axis}); "
                "cannot build one shardable batch"
            )
        batch_size = min(batch_size, max_bs)
    # caller-supplied sizes must land on a quantum multiple on BOTH
    # paths (indexed and sampled), or the compiled eval raises mid-run
    batch_size = batch_size // quantum * quantum
    if batch_size == 0:
        raise ValueError(
            f"batch_size {requested} rounds down to 0 at batch "
            f"granularity {quantum}; pass batch_size >= {quantum}"
        )
    rem_size = 0
    if capable:
        full_batches = len(dataset) // batch_size
        # trailing remainder, rounded to a shardable size: runs as one
        # extra smaller batch so coverage misses < quantum samples
        rem_size = (len(dataset) - full_batches * batch_size) // quantum * quantum
    if max_batches is None:
        if not hasattr(dataset, "__len__"):
            raise ValueError(
                f"{type(dataset).__name__} has no __len__; pass max_batches"
            )
        max_batches = full_batches if capable else max(1, len(dataset) // batch_size)
    if capable:
        max_batches = min(max_batches, full_batches)
        if max_batches < full_batches:
            rem_size = 0  # caller truncated: no remainder pass
    # "exact" promises once-per-sample coverage up to < n_axis leftovers —
    # a caller-truncated run is a sampled estimate of a different kind
    exact = capable and max_batches == full_batches
    rng = np.random.default_rng(seed)
    # eval goes through the eval pipeline; _eval_view never mutates the
    # caller's dataset, so a concurrent loader keeps augmenting
    dataset = _eval_view(dataset)
    total = {"loss": 0.0}
    n = 0

    def accumulate(draw, bs, first):
        nonlocal n
        draw = apply_transform(task.transform, draw)
        batch = sharding_lib.shard_batch(
            batch_to_dict(draw, getattr(dataset, "nclasses", None)), task.mesh,
            axis=getattr(task, "batch_axes", mesh_lib.DATA_AXIS),
        )
        loss, accs = task.eval_fn(task.state, batch)
        if first:
            _require_topk(accs, topk)
        total["loss"] += float(loss) * bs
        for k in topk:
            total[f"top{k}"] = (
                total.get(f"top{k}", 0.0) + float(accs[f"top{k}"]) * bs
            )
        n += bs

    for i in range(max_batches):
        if exact:
            idx = np.arange(i * batch_size, (i + 1) * batch_size)
            draw = dataset.batch(rng, batch_size, indices=idx)
        else:
            draw = dataset.batch(rng, batch_size)
        accumulate(draw, batch_size, first=i == 0)
    if exact and rem_size:
        start = max_batches * batch_size
        idx = np.arange(start, start + rem_size)
        # full_batches >= 1 on the exact path, so topk was already
        # validated by the first full batch
        accumulate(
            dataset.batch(rng, rem_size, indices=idx), rem_size, first=False
        )
    out = {key: v / max(n, 1) for key, v in total.items()}
    out["samples"] = n
    out["exact"] = exact
    if exact:
        # < quantum samples can be unreachable when the dataset size is
        # not a multiple of the batch granularity; report the honest count
        out["dropped"] = len(dataset) - n
    return out


class _RouterCounters:
    """Feeds the registry from what a step with routers reports of
    itself (``metrics["moe_load"]`` and its siblings, which
    ``lm_loss_fn`` hands the step maker for a model that has a
    ``step_metrics``): called by the completion watcher with metrics
    that are ready, so the loop waits for nothing.  A step without a
    router registers nothing."""

    def __init__(self, registry):
        self._reg = registry
        self._made = None

    def __call__(self, metrics) -> None:
        if not isinstance(metrics, dict) or "moe_load" not in metrics:
            return
        if self._made is None:
            reg = self._reg
            self._made = (
                reg.counter("fdtpu_moe_slots_total",
                            "token-slots routed to the experts held here "
                            "and to the absent ones", ("where",)),
                reg.counter("fdtpu_moe_dropped_total",
                            "token-slots of held experts that found no row"),
                reg.counter("fdtpu_moe_compact_total",
                            "expert layers of a step that ran over the "
                            "bounded buffer and over the whole one",
                            ("path",)),
                reg.counter("fdtpu_moe_buffer_rows_total",
                            "rows of the expert layers' sorted buffers "
                            "that held a slot, and rows of the buffers "
                            "the layers took", ("kind",)),
                reg.histogram(
                    "fdtpu_moe_load_max_over_mean",
                    "a step's largest expert load over its mean load",
                    ("layer",), buckets=(1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0,
                                         16.0, 64.0)))
        slots, dropped, paths, rows, balance = self._made
        load = np.asarray(metrics["moe_load"], np.float64)
        load = load.reshape((-1,) + load.shape[-2:])  # steps_per_call > 1
        held, absent = np.asarray(
            metrics["moe_slots"], np.float64).reshape(-1, 2).sum(axis=0)
        slots.labels(where="held").inc(float(held))
        slots.labels(where="absent").inc(float(absent))
        dropped.inc(float(np.sum(np.asarray(metrics["moe_dropped"]))))
        compact, full = np.asarray(
            metrics["moe_compact"], np.float64).reshape(-1, 2).sum(axis=0)
        paths.labels(path="compact").inc(float(compact))
        paths.labels(path="full").inc(float(full))
        live, taken = np.asarray(
            metrics["moe_rows"], np.float64).reshape(-1, 2).sum(axis=0)
        rows.labels(kind="live").inc(float(live))
        rows.labels(kind="taken").inc(float(taken))
        for step in load:
            for layer, row in enumerate(step):
                mean = row.mean()
                if mean > 0:
                    balance.labels(layer=layer).observe(float(row.max() / mean))


class _PhaseClock:
    """Step-phase bracketing: every ``with phases("dispatch"):`` block
    opens a span of that name in the process tracer and observes its
    wall seconds into the registry's per-phase histogram — ONE set of
    brackets feeds the live ``/metrics`` percentiles, the step timeline
    and, while a profiler session records, its ``/host:CPU`` plane.
    ``begin_item`` opens the loader item's own span around them, so
    every phase carries the item's id and names it as its parent.  When
    the backend reports HBM truth and a watchdog rides along, every
    phase exit also samples ``device.memory_stats()`` into the
    watchdog's OOM-margin gauge/alert
    (:meth:`~..obs.watchdog.StepWatchdog.note_headroom`) — per-PHASE
    sampling, because the margin is tightest inside eval/checkpoint
    phases a per-step sample would straddle."""

    def __init__(self, observation: Observation, hbm=None):
        self.tracer = get_tracer()
        self._item = None  # the open item span
        # headroom sampling only when BOTH truths exist: live memory
        # stats (hbm.available — CPU short-circuits to zero cost) and
        # a watchdog to route the alert through
        self.watchdog = (observation.watchdog
                         if hbm is not None and hbm.available else None)
        self.hist = observation.registry.histogram(
            "fdtpu_train_phase_seconds",
            "wall seconds per train-step phase "
            "(data_wait/h2d/dispatch/device/eval/checkpoint)",
            labelnames=("phase",),
        )
        # per-phase seconds since the last take() — the flight
        # recorder's per-record phase breakdown (histograms are
        # cumulative; the black box needs THIS step's split)
        self.last: dict = {}

    def take(self) -> dict:
        """Return-and-clear the per-phase seconds accumulated since the
        previous call (one flight record's phase breakdown)."""
        out, self.last = self.last, {}
        return out

    def begin_item(self, item: int, opt_step: int) -> None:
        """Close the item before and open this one: item spans tile the
        loop's wall time, so what no phase covers is the loop's own."""
        self.end_item()
        self._item = self.tracer.span(
            "item", item=item, opt_step=opt_step,
            # a profiler session slows the loop it records: readers of
            # the timeline stop at such an item
            traced=jax.profiler.TraceAnnotation.is_enabled())
        self._item.__enter__()

    def end_item(self) -> None:
        if self._item is not None:
            # a session that began during the item touched it too
            self._item.args["traced"] |= (
                jax.profiler.TraceAnnotation.is_enabled())
            self._item.__exit__(None, None, None)
            self._item = None

    @contextlib.contextmanager
    def __call__(self, name: str, **args):
        t0 = time.perf_counter()
        try:
            with self.tracer.span(name, **args):
                yield
        finally:
            # observe on the exception path too (the span does): an
            # OOM-heavy run must not show artificially fast dispatch
            # percentiles while its trace shows the slow truth
            dt = time.perf_counter() - t0
            self.hist.labels(phase=name).observe(dt)
            self.last[name] = self.last.get(name, 0.0) + dt
            if self.watchdog is not None:
                from ..obs import memstats

                self.watchdog.note_headroom(memstats.min_headroom_ratio())


@_in_span("train", lambda task, **kw: {
    "start_item": int(getattr(task.loader, "start", 0))})
def train(
    task: TrainTask,
    *,
    print_every: int = 10,
    eval_every: int = 50,
    topk: Optional[Sequence[int]] = None,
    sched: Optional[Callable] = None,
    logger: Optional[Logger] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 20,
    verbose: bool = False,
    profile_dir: Optional[str] = None,
    profile_start: int = 10,
    profile_steps: int = 5,
    observation: Optional[Observation] = None,
    handle_signals: bool = False,
    guard=None,
):
    """The training loop (``train`` src/ddp_tasks.jl:174-247).

    Cadence parity: cycle print every ``print_every`` (ref 10), val+train
    eval every ``eval_every`` (ref 50) with top-k accuracy (ref k=1,5,10),
    checkpoint every ``checkpoint_every`` cycles (ref 20, src/sync.jl:156),
    OOM-skip with a live ``num_missed`` counter (ref :230-238).

    Beyond the reference (whose only timing hook is dead code, SURVEY §5):
    steps/sec + images/sec are logged at every ``print_every`` cadence,
    and ``profile_dir`` captures a ``jax.profiler`` device trace of steps
    ``[profile_start, profile_start + profile_steps)`` for TensorBoard,
    with the python tracer off (it halved a traced ResNet-50 run) and
    the loop's ``fdtpu/<phase>`` annotations in the host plane.

    ``observation`` threads the unified observability layer
    (:mod:`fluxdistributed_tpu.obs`) through the loop.  The default
    (``None`` → :meth:`Observation.default`) lands step counters,
    per-phase wall-time histograms, compile counts and the OOM-skip
    counter in the process registry (scrapeable via ``bin/driver.py
    --metrics-port``) and every loader item's spans (``item`` with its
    ``data_wait`` / ``dispatch`` / ``eval`` / ``checkpoint`` children,
    the workers' ``assemble`` / ``h2d``, and a ``device`` span closed by
    a watcher thread when the step's metrics are ready, so the loop is
    never blocked) in the process tracer's bounded ring, at a few
    microseconds per step.  :meth:`Observation.full` additionally
    exports the ring as Chrome/Perfetto trace JSON (``trace_path``) and
    runs a stall watchdog against the rolling-median step time.

    ``handle_signals=True`` arms checkpoint-on-preemption
    (:mod:`fluxdistributed_tpu.faults`): SIGTERM/SIGINT set a flag that
    the loop checks at the next STEP BOUNDARY (state is always
    consistent there — never mid-step, never with donated buffers in
    flight), writes a blocking sharded checkpoint plus a ``RESUME.json``
    manifest (step, data-loader cursor, skipped items, mesh topology)
    into ``checkpoint_dir``, and raises :class:`~..faults.Preempted` —
    ``bin/driver.py`` maps it to exit code 75 so a supervisor requeues
    with ``--resume``.  A resumed run (:func:`resume_training`)
    continues with step-for-step identical losses.  On multi-host runs
    the flag is agreed via :func:`..parallel.multihost.agree_to_stop`
    each step, so every host checkpoints at the same boundary.

    ``guard`` (a :class:`.guard.GuardConfig`, or ``True`` for the
    defaults) arms the self-healing policy engine
    (:class:`.guard.TrainGuard`): each step's sentinel —
    ``metrics["guard"]`` when the step was compiled with
    ``prepare_training(guard=True)``, the loss otherwise — is checked
    BEFORE the new state is committed, and the guard's verdict runs
    the ladder: quarantine-and-skip the anomalous batch (the pre-step
    state continues, exactly the OOM-skip recovery contract), roll back
    to the last-good checkpoint with the cursor rewound and the
    quarantined span recorded in the RESUME manifest, or raise
    :class:`.guard.GuardHalt` when rollbacks loop without progress.
    With a ``checkpoint_dir``, the starting state is banked as a
    baseline checkpoint (so rollback always has a target, on the
    CURRENT topology even after an elastic resume), cadence
    checkpoints become blocking and each one refreshes the manifest —
    a SIGKILL at ANY point resumes onto a consistent
    (checkpoint, cursor, quarantine) triple.  Items in
    ``task.quarantined_items`` (a resumed run's manifest) or
    ``GuardConfig.quarantine`` are skipped before dispatch — which is
    also how a clean run deterministically skips the batches a guarded
    run quarantined, the loss-parity oracle the guard tests pin.

    Resume cursor: the loop starts at ``task.loader.start`` (0 for a
    fresh run; :func:`resume_training` sets it from the manifest), and
    the loader draws batches keyed by ABSOLUTE item index — parity
    holds no matter where the run was cut.

    Returns ``(host_params, host_model_state, task)`` — the host-side
    model copy the reference returns from ``train`` (:241-246).
    """
    from .. import faults as faults_lib
    from ..parallel import multihost
    logger = logger or current_logger()
    obs = observation or Observation.default()
    reg = obs.registry
    jaxmon.install(reg)  # compile counters (idempotent, process-global)
    # per-device HBM gauges (fdtpu_hbm_bytes_* at scrape time; the
    # availability flag + NaN headroom on CPU — "unavailable", never 0)
    from ..obs import memstats as memstats_lib

    hbm = memstats_lib.HbmGauges(reg)
    phases = _PhaseClock(obs, hbm=hbm)
    steps_total = reg.counter(
        "fdtpu_train_steps_total", "optimizer steps completed")
    step_hist = reg.histogram(
        "fdtpu_train_step_seconds",
        "wall seconds per loader item (= steps_per_call optimizer steps)")
    step_gauge = reg.gauge(
        "fdtpu_train_step", "optimizer steps completed this train() call")
    oom_total = reg.counter(
        "fdtpu_train_oom_skipped_total",
        "batches skipped by OOM fault tolerance")
    sink = None
    if obs.jsonl_path:
        from ..obs import JsonlSink

        sink = JsonlSink(obs.jsonl_path, reg)
    # black-box flight recorder (obs/flight.py): per-step records that
    # survive a SIGKILL minus at most one flush interval; the dump in
    # the finally block stamps every SOFT exit's status — a footer-less
    # dump is itself the hard-death signature the postmortem keys on
    flight = obs.flight
    if flight is None and obs.flight_path:
        from ..obs.flight import FlightRecorder

        flight = FlightRecorder(obs.flight_path,
                                meta={"component": "train"})
    # the fdtpu_run_info stitch gauge: fingerprint/jax/schema labels
    # joining this registry's scrapes to flight dumps and ledger rows
    from ..obs import runs as runs_lib

    runs_lib.set_run_info(reg, "train")
    marked_steady = False
    if topk is None:
        # report exactly the metrics compiled into the task's eval step
        # (loss-only for the LM pipeline modes)
        topk = getattr(task, "topk", (1, 5, 10))
    # perf_counter, not time.time(): the loop's rate/interval math must
    # be monotonic (NTP steps or DST jumps would corrupt steps/sec and
    # the span timeline) — lint rule FDT102
    t_start = time.perf_counter()
    profiling = False
    # device loop: each loader item is K stacked batches = K optimizer
    # steps in one dispatch; cadences below tick per ITEM (= per K steps)
    spc = getattr(task, "steps_per_call", 1)
    if obs.watchdog is not None:
        obs.watchdog.start()
    # closes each item's ``device`` span when its step has finished, from
    # a thread of its own: the loop is never blocked to learn it
    watcher = CompletionWatcher(
        phases.tracer, phases.hist.labels(phase="device").observe,
        on_value=_RouterCounters(reg),
        on_ahead=reg.gauge(
            "fdtpu_train_items_ahead",
            "loader items handed to the device and not yet complete when "
            "the newest was handed over (how far the loop runs ahead)").set)

    it = iter(task.loader)
    _end = object()
    last_batch = None  # the profile artifact prices the step at these shapes
    start_item = int(getattr(task.loader, "start", 0))
    j = start_item
    t_mark, j_mark = t_start, start_item
    done_steps = 0  # optimizer steps that actually ran (skips excluded)
    preempt = faults_lib.SignalFlag().install() if handle_signals else None
    # eval and checkpoint are KNOWN-long in-loop work: suspend stall
    # detection around them (a 2 s checkpoint snapshot in a 100 ms-step
    # run must not flip /healthz to 503)
    wd_pause = (obs.watchdog.pause if obs.watchdog is not None
                else contextlib.nullcontext)

    # -- self-healing guard (train/guard.py) ---------------------------
    guard_obj = None
    if guard is not None and guard is not False:
        from .guard import GuardConfig, TrainGuard

        cfg = guard if isinstance(guard, GuardConfig) else GuardConfig()
        guard_obj = TrainGuard(cfg, registry=reg, logger=logger)
        # decisions recorded by a previous process (the RESUME manifest
        # resume_training read) replay deterministically
        for q in getattr(task, "quarantined_items", []):
            if not guard_obj.is_quarantined(q):
                guard_obj.quarantine(q)
    # the rollback target: the newest checkpoint and the loader item a
    # resume from it must start at — kept consistent with what is ON
    # DISK (only ever updated after a blocking save)
    last_good: Optional[dict] = None

    def _run_manifest(reason: str, checkpoint_step: int,
                      next_item: int) -> dict:
        m = {
            "version": 1,
            "reason": reason,
            "checkpoint_step": int(checkpoint_step),
            "next_item": int(next_item),
            "steps_per_call": spc,
            "num_missed": int(task.num_missed),
            "skipped_items": [int(x) for x in task.skipped_items],
            "mesh": {k: int(v) for k, v in dict(task.mesh.shape).items()},
            "device_count": jax.device_count(),
            "process_count": jax.process_count(),
            # how the two rng streams re-derive on resume — both are
            # keyed on restored values, so no rng state needs saving
            "rng": {
                "step": "fold_in(PRNGKey(seed), state.step), in-graph",
                "loader": "np.random.default_rng((seed, process, item))",
            },
        }
        if guard_obj is not None:
            m["quarantined_items"] = guard_obj.quarantined_items()
        return m

    def _write_guard_manifest() -> None:
        """Persist the guard's (checkpoint, cursor, quarantine) triple
        eagerly: a SIGKILL after a quarantine/rollback decision must
        resume onto the SAME decision, not re-derive the cursor from a
        step counter the skips have desynchronized."""
        if guard_obj is None or not checkpoint_dir or last_good is None:
            return
        from .checkpoint import write_resume_manifest

        write_resume_manifest(
            checkpoint_dir,
            _run_manifest("guard", last_good["step"], last_good["item"]))

    if guard_obj is not None and checkpoint_dir:
        from .checkpoint import save_checkpoint

        # bank the starting state as the first last-good checkpoint:
        # rollback needs a target from item 0 on, and re-saving on a
        # RESUMED run keeps the target on the CURRENT topology (after
        # an elastic resume, the previous run's checkpoint has the old
        # device count's ZeRO-1 flat-pad layout — rolling back onto it
        # would need the elastic path; re-banking makes every rollback
        # a plain same-topology restore)
        with wd_pause(), phases("checkpoint"):
            known = int(task.state.step)
            save_checkpoint(task.state, checkpoint_dir, known, block=True)
        last_good = {"step": known, "item": start_item}
        _write_guard_manifest()
    elif guard_obj is not None:
        logger.info(
            "guard: no checkpoint_dir — the rollback tier is disabled, "
            "the policy ladder is skip-and-quarantine -> halt")

    def _preempted() -> bool:
        if preempt is None or not handle_signals:
            return False
        hit = preempt.is_set()
        if jax.process_count() > 1:
            # every host must agree on the boundary, or one host enters
            # the collective checkpoint save the others never join
            hit = multihost.agree_to_stop(hit)
        return hit

    def _checkpoint_and_exit() -> None:
        """The checkpoint-on-signal exit: blocking sharded save + an
        atomically-written RESUME manifest, then a distinct signal to
        the caller (``Preempted`` → driver rc 75)."""
        from .checkpoint import save_checkpoint, write_resume_manifest

        step_now = int(task.state.step)
        manifest = _run_manifest(
            preempt.reason if preempt is not None else "requested",
            step_now, j)
        if checkpoint_dir:
            with wd_pause(), phases("checkpoint"):
                # blocking: the process is about to exit — an async
                # write would race the runtime teardown
                save_checkpoint(task.state, checkpoint_dir, step_now,
                                block=True)
                write_resume_manifest(checkpoint_dir, manifest)
            faults_lib.record_preemption()
            logger.info(
                f"preempted ({manifest['reason']}): checkpointed step "
                f"{step_now} + RESUME manifest (next item {j}) in "
                f"{checkpoint_dir}")
        else:
            logger.info(
                f"preempted ({manifest['reason']}) with no "
                "checkpoint_dir — nothing persisted, the run cannot "
                "be resumed")
        raise faults_lib.Preempted(
            f"training preempted at step {step_now} (item {j})",
            step=step_now, next_item=j, checkpoint_dir=checkpoint_dir,
            manifest=manifest)

    try:
        while True:
            # deterministic injection point for SIGTERM-at-step-k (the
            # fault plan delivers the signal; the very next check sees
            # it) — and THE step-boundary preemption check: state here
            # is consistent, no donated buffers are in flight
            phases.begin_item(j, done_steps)
            faults_lib.fire("step", index=j)
            if _preempted():
                _checkpoint_and_exit()
            t_item = time.perf_counter()
            # data_wait: host time BLOCKED on the prefetch queue — nonzero
            # percentiles here mean the input pipeline, not the model, is
            # the bottleneck (the h2d copy itself is timed loader-side)
            with phases("data_wait"):
                batch = next(it, _end)
            if batch is _end:
                break
            last_batch = batch
            if print_every and j % print_every == 0:
                now = time.perf_counter()
                if j > j_mark:
                    # interval rates; the loop can only run ahead of the device
                    # by the dispatch queue, so interval averages are accurate
                    dsteps = (j - j_mark) * spc
                    dt = max(now - t_mark, 1e-9)
                    lead = jax.tree.leaves(batch)[0]
                    gbatch = int(lead.shape[1] if spc > 1 else lead.shape[0])
                    logger.log(
                        {
                            "steps_per_sec": round(dsteps / dt, 3),
                            "images_per_sec": round(dsteps * gbatch / dt, 1),
                        },
                        j,
                    )
                    t_mark, j_mark = now, j
                logger.info(f"cycle {j} (t={now - t_start:.1f}s)")
                if sink is not None:
                    sink.write(step=j * spc)
            if profile_dir is not None:
                if j == profile_start:
                    # the python tracer halved a traced ResNet-50 run
                    # (PERF.md); the host tracer keeps the loop's own
                    # fdtpu/<phase> annotations
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(profile_dir,
                                             profiler_options=opts)
                    profiling = True
                elif profiling and j == profile_start + profile_steps:
                    tree_lib.synchronize(task.state.params)
                    jax.profiler.stop_trace()
                    profiling = False
                    logger.info(f"profiler trace written to {profile_dir}")
            if sched is not None:
                lr = sched(j * spc)  # optimizer-step units, not loader items
                if verbose and lr is not None:
                    logger.log({"lr": float(lr)}, j)
            if (obs.steady_after is not None and not marked_steady
                    and j >= obs.steady_after):
                # warmup declared over: any further XLA compile is flagged
                # as a steady-state recompile (live metric + warning)
                jaxmon.mark_steady()
                marked_steady = True
            skipped = False
            verdict = None
            if guard_obj is not None and guard_obj.is_quarantined(j):
                # pre-step quarantine skip: the batch was drawn (the
                # data cursor must advance exactly as it did when the
                # quarantine was decided) but is never stepped — the
                # deterministic replay of a guard decision, and the
                # clean-run oracle's way to skip the same batch
                guard_obj.note_replayed_skip(j)
                logger.info(f"cycle {j}: guard — quarantined batch skipped")
                skipped = True
            else:
                # the try covers ONLY dispatch + sentinel read: recovery
                # actions (rollback restore, halt) run after it, so a
                # failure inside them can never be mistaken for a
                # skippable per-batch OOM
                try:
                    if verbose:
                        logger.info(f"  step {j}: dispatching compiled SPMD step")
                    # dispatch: host-side time to enqueue the compiled step
                    # (any XLA compile on first touch, and any wait for the
                    # device to take it); the watcher then closes the item's
                    # device span when these metrics are ready
                    with phases("dispatch"):
                        new_state, metrics = task.step_fn(task.state, batch)
                        if guard_obj is None:
                            task.state = new_state
                    watcher.watch(j, metrics, time.perf_counter())
                    if guard_obj is not None:
                        # verdict BEFORE commit: an anomalous step's output
                        # is discarded and the pre-step state lives on
                        verdict = guard_obj.observe(
                            j, metrics, can_rollback=last_good is not None)
                        if verdict == "ok":
                            task.state = new_state
                        else:
                            # the task mirrors the guard's record, so
                            # callers (and the preemption manifest) see
                            # decisions without reaching into guard_obj
                            task.quarantined_items = (
                                guard_obj.quarantined_items())
                            if state_donated(task.state):
                                raise RuntimeError(
                                    "guard anomaly with donate=True: the "
                                    "pre-step state was donated to the "
                                    "anomalous step and cannot be recovered "
                                    "— re-run prepare_training(donate=False)")
                except Exception as e:  # OOM-skip fault tolerance
                    if _is_oom(e):
                        if jax.process_count() > 1:
                            # Single-host-only semantics, like the reference (skip
                            # exists in task mode src/ddp_tasks.jl:230-238, NOT in
                            # process mode src/sync.jl): a one-sided skip would
                            # desynchronize step counts across hosts and strand
                            # the others in a collective this host never enters.
                            raise RuntimeError(
                                "device OOM on a multi-host run: batch skipping "
                                "cannot be coordinated one-sidedly — reduce the "
                                "per-host batch size"
                            ) from e
                        if state_donated(task.state):
                            raise RuntimeError(
                                "device OOM with donate=True: the training state was "
                                "donated to the failed step and cannot be recovered — "
                                "re-run prepare_training(donate=False) for OOM-skip"
                            ) from e
                        task.num_missed += spc
                        task.skipped_items.append(j)
                        oom_total.inc(spc)
                        # the skipped batch's GLOBAL indices go on record:
                        # the data cursor advances past it (j increments
                        # below as for any item), so a resume after this
                        # skip replays the exact same remaining stream —
                        # and the log says which samples training never saw
                        logger.log(
                            {"oom_skipped_item": j,
                             "oom_skipped_step_first": j * spc}, j)
                        logger.info(f"cycle {j}: device OOM — skipping batch ({task.num_missed} missed)")
                        skipped = True
                    else:
                        raise
            # guard verdict execution — OUTSIDE the OOM-skip try: a
            # failure while restoring a checkpoint must surface, never
            # read as "skip this batch and continue on a half-restored
            # state"
            if verdict == "skip":
                skipped = True
                _write_guard_manifest()
            elif verdict == "rollback":
                from .checkpoint import load_checkpoint, wait_for_pending

                logger.info(
                    f"guard: rolling back to checkpoint step "
                    f"{last_good['step']} (item {last_good['item']}); "
                    f"quarantined {guard_obj.quarantined_items()}")
                with wd_pause(), phases("checkpoint"):
                    wait_for_pending()
                    task.state = load_checkpoint(
                        checkpoint_dir, task.state,
                        step=last_good["step"], mesh=task.mesh)
                _write_guard_manifest()
                # rewind the data cursor and replay — the quarantined
                # span skips on the way through
                it.close()
                task.loader.start = last_good["item"]
                it = iter(task.loader)
                j = last_good["item"]
                continue
            elif verdict == "halt":
                _write_guard_manifest()
                raise guard_obj.halt(
                    "anomalies persist across "
                    f"{guard_obj._rollbacks} rollback(s)"
                    if last_good is not None else
                    "rollback needed but no checkpoint_dir to "
                    "roll back to")
            if not skipped:
                if eval_every and j % eval_every == 0:
                    with wd_pause(), phases("eval"):
                        if task.val_batch is not None:
                            _eval_and_log(task, task.val_batch, "val", j, topk, logger)
                        # chunked items carry K batches; eval the last sub-batch (the
                        # eval step is compiled for the per-step layout)
                        eb = jax.tree.map(lambda x: x[-1], batch) if spc > 1 else batch
                        _eval_and_log(task, eb, "train", j, topk, logger)
                        loss_m = metrics["loss"]
                        last_loss = loss_m[-1] if getattr(loss_m, "ndim", 0) else loss_m
                        logger.log({"train_step_loss": float(last_loss)}, j)
                if checkpoint_dir and checkpoint_every and j > 0 and j % checkpoint_every == 0:
                    from .checkpoint import save_checkpoint

                    # async write: the device→host snapshot happens now, the disk
                    # write overlaps subsequent steps (drained before exit below).
                    # Guarded runs save BLOCKING instead: last_good must only
                    # ever name a checkpoint that is durably on disk — a
                    # rollback (or a SIGKILL resume) onto a still-streaming
                    # save would read garbage the atomicity protocol hides
                    # but the cursor math would still trust
                    with wd_pause(), phases("checkpoint"):
                        save_checkpoint(task.state, checkpoint_dir,
                                        int(task.state.step),
                                        block=guard_obj is not None)
                    if guard_obj is not None:
                        last_good = {"step": int(task.state.step),
                                     "item": j + 1}
                        _write_guard_manifest()
                steps_total.inc(spc)
                done_steps += spc
                step_gauge.set(done_steps)
                step_hist.observe(time.perf_counter() - t_item)
            if obs.watchdog is not None:
                # a skipped batch is still loop progress — the watchdog
                # hunts wedged loops, not lost work (that's the counter)
                obs.watchdog.beat()
            if flight is not None:
                # the black box's per-step record: everything a
                # postmortem needs to name where and how this step went.
                # record() never raises; the assembly below must not
                # either — forensics can't be the thing that kills
                # the flight
                try:
                    frec: dict = {
                        "step": j,
                        "opt_step": done_steps,
                        "phases": {k: round(v, 4)
                                   for k, v in phases.take().items()},
                    }
                    if skipped:
                        frec["skipped"] = True
                    else:
                        try:
                            lm = metrics["loss"]
                            frec["loss"] = float(
                                lm[-1] if getattr(lm, "ndim", 0) else lm)
                        except Exception:  # noqa: BLE001
                            pass
                    if verdict is not None:
                        frec["guard_verdict"] = verdict
                        z = reg.value("fdtpu_guard_last_z")
                        if z is not None:
                            frec["guard_z"] = round(float(z), 3)
                    if hbm.available:
                        hr = memstats_lib.min_headroom_ratio()
                        if hr == hr:  # NaN = unavailable, not 0
                            frec["headroom"] = round(hr, 4)
                    compiles = reg.value("fdtpu_jax_compiles_total")
                    if compiles:
                        frec["compiles"] = int(compiles)
                    sr = reg.value("fdtpu_jax_steady_recompiles_total")
                    if sr:
                        frec["steady_recompiles"] = int(sr)
                    if task.num_missed:
                        frec["oom_skipped"] = int(task.num_missed)
                    stalled = reg.value("fdtpu_watchdog_stalled")
                    if stalled:
                        frec["stalled"] = int(stalled)
                    flight.record(**frec)
                except Exception:  # noqa: BLE001 — never kill the loop
                    pass
            j += 1
    finally:
        if flight is not None:
            # stamp every SOFT exit's verdict into the footer (a
            # SIGKILL never reaches here — the footer-less dump is
            # exactly the hard-death signature read_flight reports)
            try:
                etype, evalue = sys.exc_info()[:2]
                if etype is None:
                    flight.dump("done", steps=done_steps)
                elif issubclass(etype, faults_lib.Preempted):
                    flight.dump("preempted", error=str(evalue),
                                steps=done_steps)
                else:
                    from .guard import GuardHalt

                    flight.dump(
                        "halt" if issubclass(etype, GuardHalt)
                        else "crash",
                        error=f"{etype.__name__}: {evalue}",
                        steps=done_steps)
            except Exception:  # noqa: BLE001
                pass
        if preempt is not None:
            preempt.uninstall()
        if obs.watchdog is not None:
            obs.watchdog.stop()
        if marked_steady:
            jaxmon.clear_steady()
        phases.end_item()
        if not watcher.close():
            logger.info("the last steps did not complete within the "
                        "watcher's time limit: their device spans are missing")
        if obs.trace_path:
            # export even on an exception: the timeline UP TO a crash
            # is exactly what the postmortem needs
            n = phases.tracer.export_chrome_trace(obs.trace_path)
            logger.info(f"span trace ({n} events) written to {obs.trace_path}")
        if obs.profile_path:
            # the planner-facing artifact: static per-layer/step costs
            # at this run's real shapes + the measured phase histograms.
            # Best-effort on purpose — a finished (or crashed) training
            # run must never be failed retroactively by its profiler
            from ..obs import profile as profile_lib

            try:
                prof = profile_lib.collect_profile(
                    task, registry=reg, batch=last_batch,
                    meta={"steps": done_steps, "steps_per_call": spc})
                prof.save(obs.profile_path)
                logger.info(f"cost profile written to {obs.profile_path}")
            except Exception as e:  # noqa: BLE001
                logger.info(f"cost profile collection failed: "
                            f"{type(e).__name__}: {e}")
        if sink is not None:
            sink.write(step=j * spc, final=True)

    if profiling:
        tree_lib.synchronize(task.state.params)
        jax.profiler.stop_trace()
        logger.info(f"profiler trace written to {profile_dir}")
    if task.num_missed:
        logger.info(f"missed {task.num_missed} batches due to OOM")
    if checkpoint_dir:
        from .checkpoint import clear_resume_manifest, wait_for_pending

        wait_for_pending()
        # a COMPLETED run must not leave a mid-run cursor behind: a
        # later --resume would trust it and skip work
        clear_resume_manifest(checkpoint_dir)
    host_params = tree_lib.to_host(task.state.params)
    host_mstate = tree_lib.to_host(task.state.model_state)
    return host_params, host_mstate, task
