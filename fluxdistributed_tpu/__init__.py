"""fluxdistributed_tpu — a TPU-native data-parallel training framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
``DhairyaLGandhi/FluxDistributed.jl`` (the reference): data-parallel
training of vision models on ImageNet across a device mesh, with the
input pipeline, eval/metrics, logging, checkpointing and fault handling
that surround it — built TPU-first (SPMD over ``jax.sharding.Mesh``,
compiled collectives over ICI/DCN, bf16 on the MXU) rather than as a
port of the reference's task/process + hub-reduce machinery.

The package targets full parity with the reference's exported surface
(src/FluxDistributed.jl:11-12) re-shaped for JAX; the names exported
below are the currently implemented subset.
"""

from . import (
    compilation,
    data,
    faults,
    mesh,
    models,
    obs,
    ops,
    optim,
    parallel,
    sharding,
    tree,
)


def __getattr__(name):
    # ``train`` is lazy (PEP 562): it imports orbax.checkpoint, which
    # costs seconds at startup that data/mesh/ops-only consumers never
    # need to pay
    if name == "train":
        import importlib

        mod = importlib.import_module(".train", __name__)
        globals()["train"] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
from .data import (
    labels,
    load_registry,
    minibatch,
    open_dataset,
    preprocess,
    register_dataset,
    train_solutions,
)
from .mesh import data_mesh, make_mesh
from .ops import logitcrossentropy, topkaccuracy, onehot
from .parallel import (
    TrainState,
    make_eval_step,
    make_train_step,
    make_train_step_shardmap,
    pmean,
    psum,
)
from .parallel.dp import flax_loss_fn

__version__ = "0.1.0"

__all__ = [
    "compilation",
    "data",
    "mesh",
    "models",
    "obs",
    "ops",
    "optim",
    "parallel",
    "sharding",
    "train",
    "tree",
    "labels",
    "load_registry",
    "minibatch",
    "open_dataset",
    "preprocess",
    "register_dataset",
    "train_solutions",
    "data_mesh",
    "make_mesh",
    "logitcrossentropy",
    "topkaccuracy",
    "onehot",
    "TrainState",
    "make_train_step",
    "make_train_step_shardmap",
    "make_eval_step",
    "flax_loss_fn",
    "pmean",
    "psum",
]
