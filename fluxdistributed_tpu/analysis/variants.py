"""The jaxpr-layer check targets: every registered train-step variant +
the serve engine's compiled program pool, built tiny on the 8-virtual-
device CPU mesh.

Each entry goes through the REAL registered path — ``prepare_training``
for the parallelism modes, ``LMEngine`` for serving — with toy model
sizes, so what the static layer validates is exactly the code a real run
compiles: the step factories, the sharding layouts, the donation
vectors.  Nothing here executes a step by default (building a variant
traces nothing); the jaxpr checks lower/abstract-eval the returned
callables on CPU in seconds where a hardware bench round would burn
minutes discovering the same bug.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["StepVariant", "VARIANT_BUILDERS", "variant_names", "build_variants"]


@dataclasses.dataclass
class StepVariant:
    """One compiled-program check target.

    ``fn(*args)`` is the jit-wrapped program; ``donate_argnums`` is what
    the variant DECLARES it donates (the jaxpr layer verifies the
    declaration is consumable); ``source`` is the repo-relative file of
    the factory the findings should point at.  ``execute=True`` marks
    the variant cheap enough for the optional transfer-guard execution
    check (one real compiled step on CPU)."""

    name: str
    fn: Callable
    args: Tuple[Any, ...]
    donate_argnums: Tuple[int, ...]
    mesh: Any
    source: str
    execute: bool = False
    #: thread one call's outputs into the next call's arguments — the
    #: steady-state input for the guarded second call of the transfer
    #: check (required for executing variants that donate buffers)
    carry: Optional[Callable[[Tuple, Any], Tuple]] = None


def _src(module) -> str:
    """Repo-relative path of a module's source file."""
    from .engine import repo_root

    path = os.path.abspath(module.__file__)
    try:
        rel = os.path.relpath(path, repo_root())
    except ValueError:
        rel = path
    return rel.replace(os.sep, "/")


def _image_setup():
    from ..data.synthetic import SyntheticDataset
    from ..models.simple import SimpleCNN

    return (SimpleCNN(num_classes=4, features=8),
            SyntheticDataset(nsamples=64, nclasses=4, shape=(8, 8, 3)))


def _lm_setup(depth: int, heads: int, attn_fn=None):
    import jax.numpy as jnp

    from ..data.synthetic import SyntheticTextDataset
    from ..models.transformer_lm import TransformerLM

    model = TransformerLM(
        vocab=32, dim=16, depth=depth, num_heads=heads, mlp_dim=32,
        dtype=jnp.float32, dropout=0.0, attn_fn=attn_fn)
    return model, SyntheticTextDataset(vocab=32, seqlen=16)


def _prepared(name: str, model, dataset, mesh, source_mod,
              execute: bool = False, **kw) -> List[StepVariant]:
    """Run the real ``prepare_training`` path and wrap its compiled step
    as a check target (donate=True so the donation vector is live)."""
    from .. import optim
    from ..train.trainer import _dummy_batch, prepare_training

    task = prepare_training(
        model, dataset, optim.adam(1e-3), mesh=mesh, batch_size=16,
        cycles=1, donate=True, **kw)
    # the task's batch axes, not a hardcoded one: the 3-D layouts
    # shard batches over (data, fsdp) jointly
    batch = _dummy_batch(dataset, None, 16, mesh, 1, seed=0,
                         axis=task.batch_axes)
    return [StepVariant(
        name=name, fn=task.step_fn, args=(task.state, batch),
        donate_argnums=(0,), mesh=mesh, source=_src(source_mod),
        execute=execute,
        # (state, batch) → ((new_state, metrics)) → (new_state, batch)
        carry=lambda args, out: (out[0], args[1]))]


def _build_dp() -> List[StepVariant]:
    from .. import mesh as mesh_lib
    from ..parallel import dp

    model, ds = _image_setup()
    return _prepared("dp", model, ds, mesh_lib.data_mesh(8), dp,
                     execute=True, spmd="jit")


def _build_zero1() -> List[StepVariant]:
    from .. import mesh as mesh_lib
    from ..parallel import zero1

    model, ds = _image_setup()
    return _prepared("zero1", model, ds, mesh_lib.data_mesh(8), zero1,
                     execute=True, spmd="jit", zero1=True)


def _build_dp_shardmap() -> List[StepVariant]:
    """The explicit-collectives DP step (``spmd="shard_map"``): per-
    device grads + pmean written out as real collective primitives.
    Registered so the comms ledger's jaxpr layer sees DP's semantic
    signature — all-reduce ONLY — on a real ``prepare_training`` path
    (the GSPMD dp variant's jaxpr carries no collectives; XLA inserts
    them at compile time)."""
    from .. import mesh as mesh_lib
    from ..parallel import dp

    model, ds = _image_setup()
    return _prepared("dp_shardmap", model, ds, mesh_lib.data_mesh(8), dp,
                     execute=True, spmd="shard_map")


def _build_zero1_shardmap() -> List[StepVariant]:
    """The explicit-collectives ZeRO-1 step (``spmd="shard_map",
    zero1=True``): reduce-scatter → slice-local update → all-gather,
    the arXiv:2004.13336 schedule written out.  Registered so the
    comms ledger can assert the paper's signature (reduce-scatter +
    all-gather where dp shows all-reduce) on the real path."""
    from .. import mesh as mesh_lib
    from ..parallel import zero1

    model, ds = _image_setup()
    return _prepared("zero1_shardmap", model, ds, mesh_lib.data_mesh(8),
                     zero1, execute=True, spmd="shard_map", zero1=True)


def _build_fsdp() -> List[StepVariant]:
    """ZeRO-3 over all 8 devices: the one-rule fsdp table."""
    from ..parallel import layout as layout_mod

    model, ds = _image_setup()
    lay = layout_mod.resolve_layout("fsdp", 8)
    return _prepared("fsdp", model, ds, lay.build_mesh(), layout_mod,
                     execute=True, layout=lay)


def _build_tp() -> List[StepVariant]:
    """Megatron tensor parallelism, dp=2 x tp=4: the lm_tp table."""
    from ..models.transformer_lm import lm_loss_fn
    from ..parallel import layout as layout_mod

    model, ds = _lm_setup(depth=1, heads=4)
    lay = layout_mod.Layout("tp", dp=2, tp=4)
    return _prepared("tp", model, ds, lay.build_mesh(), layout_mod,
                     layout=lay, loss_fn=lm_loss_fn(model), topk=())


def _build_pp_1f1b() -> List[StepVariant]:
    from .. import mesh as mesh_lib
    from ..parallel import pp_1f1b

    mesh = mesh_lib.make_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.PIPE_AXIS: 4})
    model, ds = _lm_setup(depth=4, heads=2)
    return _prepared("pp_1f1b", model, ds, mesh, pp_1f1b,
                     spmd="pp_1f1b", num_microbatches=2, topk=())


def _build_pp_planned() -> List[StepVariant]:
    """The planner-placed pipeline: depth 6 over 4 pipe devices via a
    non-uniform PipelinePlan (counts [1, 2, 2, 1] — padded chunk scan,
    cond-skipped idle chunks, lifted depth-divisibility requirement).  Sweeping
    it proves the counts-aware ``chunk_stages`` program keeps the pp
    invariants: donation consumable, axis hygiene, stable retrace
    digests (the counts table is baked, never an argument)."""
    from .. import mesh as mesh_lib
    from ..parallel import pp_plan as pp_plan_mod

    mesh = mesh_lib.make_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.PIPE_AXIS: 4})
    model, ds = _lm_setup(depth=6, heads=2)
    # flat block costs + outer weight on the end stages -> the planner
    # thins the first/last stage: boundaries (0, 1, 3, 5, 6)
    plan = pp_plan_mod.plan_stages(
        [1.0] * 6, 4, 2, outer=(1.0, 1.0))
    return _prepared("pp_planned", model, ds, mesh, pp_plan_mod,
                     spmd="pp_1f1b", num_microbatches=2, topk=(),
                     pp_plan=plan)


def _build_pp_zb() -> List[StepVariant]:
    """The zero-bubble schedule (pp_1f1b ``schedule="zb"``): B/W-split
    backward, cot-stash ring riding the scan carry.  Swept so the W
    tick's cond branches and the extra carry keep donation/axis/retrace
    hygiene."""
    from .. import mesh as mesh_lib
    from ..parallel import pp_1f1b

    mesh = mesh_lib.make_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.PIPE_AXIS: 4})
    model, ds = _lm_setup(depth=4, heads=2)
    return _prepared("pp_zb", model, ds, mesh, pp_1f1b,
                     spmd="pp_1f1b", num_microbatches=2, topk=(),
                     pipeline_schedule="zb")


def _build_layout_dp_fsdp() -> List[StepVariant]:
    """The rule-derived 2-D layout (dp=2 x fsdp=4) on the image model:
    the EMPTY rule table + the ShardLargest fsdp overlay shards a conv
    stack with no per-model spec code — swept so the 3-D mesh step
    keeps donation/axis/retrace hygiene."""
    from .. import mesh as mesh_lib  # noqa: F401 — axis constants source
    from ..parallel import layout as layout_mod

    model, ds = _image_setup()
    lay = layout_mod.resolve_layout("dp_fsdp", 8)
    return _prepared("layout_dp_fsdp", model, ds, lay.build_mesh(),
                     layout_mod, execute=True, layout=lay)


def _build_layout_fsdp_tp() -> List[StepVariant]:
    """fsdp=4 x tp=2 on the LM: the committed lm_tp rule table decides
    the Megatron dims, the overlay ZeRO-shards the leftovers — the 2-D
    large-model recipe."""
    from ..models.transformer_lm import lm_loss_fn
    from ..parallel import layout as layout_mod

    model, ds = _lm_setup(depth=1, heads=4)
    lay = layout_mod.resolve_layout("fsdp_tp", 8)
    return _prepared("layout_fsdp_tp", model, ds, lay.build_mesh(),
                     layout_mod, layout=lay,
                     loss_fn=lm_loss_fn(model), topk=())


def _build_layout_dp_fsdp_tp() -> List[StepVariant]:
    """The full 3-D composition dp=2 x fsdp=2 x tp=2 — one mesh, one
    rule table, all three parallelism families at once (the
    arXiv:1810.09868 full-program partitioning thesis, exercised on
    the real prepare_training path)."""
    from ..models.transformer_lm import lm_loss_fn
    from ..parallel import layout as layout_mod

    model, ds = _lm_setup(depth=1, heads=4)
    lay = layout_mod.resolve_layout("dp_fsdp_tp", 8)
    return _prepared("layout_dp_fsdp_tp", model, ds, lay.build_mesh(),
                     layout_mod, layout=lay,
                     loss_fn=lm_loss_fn(model), topk=())


def _build_context() -> List[StepVariant]:
    from .. import mesh as mesh_lib
    from ..models.transformer_lm import lm_loss_fn
    from ..parallel import context

    mesh = mesh_lib.make_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.SEQ_AXIS: 4})
    model, ds = _lm_setup(
        depth=1, heads=4,
        attn_fn=context.make_ring_attention(
            mesh, batch_axis=mesh_lib.DATA_AXIS, causal=True))
    return _prepared("context", model, ds, mesh, context, spmd="sp",
                     loss_fn=lm_loss_fn(model), topk=())


def _build_serve() -> List[StepVariant]:
    """The engine's per-program pool: one prefill per bucket, the slot
    splice, the all-slot decode step — with the donation vectors the
    engine declares (cache/token/key state updated in place)."""
    import jax

    from ..serve import engine as engine_mod

    model, _ = _lm_setup(depth=1, heads=2)
    params = model.init(jax.random.PRNGKey(0),
                        jax.numpy.zeros((1, 8), "int32"), train=False)["params"]
    eng = engine_mod.LMEngine(model, params, max_slots=2, max_len=64,
                              buckets=(16, 32))
    src = _src(engine_mod)
    out = [
        StepVariant(name="serve:step", fn=eng._step_jit,
                    args=eng._example_args("step"),
                    donate_argnums=(1, 2, 4), mesh=None, source=src,
                    # (params, cache, tok, temp, keys) → (cache', tok', keys')
                    carry=lambda a, o: (a[0], o[0], o[1], a[3], o[2])),
        StepVariant(name="serve:insert", fn=eng._insert_jit,
                    args=eng._example_args("insert"),
                    donate_argnums=(0,), mesh=None, source=src,
                    # (big, small, slot, plen) → spliced big cache
                    carry=lambda a, o: (o, a[1], a[2], a[3])),
    ]
    for b in eng.buckets:
        out.append(StepVariant(
            name=f"serve:prefill_b{b}", fn=eng._prefill_jit,
            args=eng._example_args("prefill", b),
            donate_argnums=(), mesh=None, source=src))
    return out


def _build_serve_paged() -> List[StepVariant]:
    """The paged-layout engine's program pool: the all-slot decode step,
    the prefill chunk, and the page-table maintenance programs (bind /
    release) — with the donation vectors the engine declares.  Page
    indirection must stay DATA: the jaxpr checks verify the pool's
    retrace digests are stable, i.e. page-table churn compiles nothing."""
    import jax

    from ..serve import engine as engine_mod

    model, _ = _lm_setup(depth=1, heads=2)
    params = model.init(jax.random.PRNGKey(0),
                        jax.numpy.zeros((1, 8), "int32"), train=False)["params"]
    eng = engine_mod.LMEngine(model, params, max_slots=2, max_len=64,
                              layout="paged", kv_block_size=8,
                              prefill_chunk=16, prefix_cache=True)
    src = _src(engine_mod)
    return [
        StepVariant(name="serve_paged:step", fn=eng._step_jit,
                    args=eng._example_args("step"),
                    donate_argnums=(1, 2, 4), mesh=None, source=src,
                    # (params, cache, tok, temp, keys) → (cache', tok', keys')
                    carry=lambda a, o: (a[0], o[0], o[1], a[3], o[2])),
        StepVariant(name="serve_paged:chunk", fn=eng._chunk_jit,
                    args=eng._example_args("chunk"),
                    donate_argnums=(1,), mesh=None, source=src,
                    # (params, cache, toks, slot, start, nvalid, arm) →
                    #     (cache', last_logits)
                    carry=lambda a, o: (a[0], o[0]) + a[2:]),
        StepVariant(name="serve_paged:bind", fn=eng._bind_jit,
                    args=eng._example_args("bind"),
                    donate_argnums=(0,), mesh=None, source=src,
                    # (cache, slot, page_row) → cache'
                    carry=lambda a, o: (o,) + a[1:]),
        StepVariant(name="serve_paged:release", fn=eng._release_jit,
                    args=eng._example_args("release"),
                    donate_argnums=(0,), mesh=None, source=src,
                    carry=lambda a, o: (o,) + a[1:]),
    ]


def _build_serve_paged_pallas() -> List[StepVariant]:
    """The paged pool again, but decoding through the Pallas fast path
    with a quantized (int8) KV cache — the kernel-suite configuration
    (ops/pallas_decode.py).  Sweeping it proves the flash-decode branch
    keeps the paged invariants the XLA branch established: donation
    vectors consumable, page indirection pure DATA (stable retrace
    digests — kernel dispatch cannot break AOT keys), axis hygiene."""
    import jax

    from ..serve import engine as engine_mod

    model, _ = _lm_setup(depth=1, heads=2)
    params = model.init(jax.random.PRNGKey(0),
                        jax.numpy.zeros((1, 8), "int32"), train=False)["params"]
    eng = engine_mod.LMEngine(model, params, max_slots=2, max_len=64,
                              layout="paged", kv_block_size=8,
                              prefill_chunk=16, attention_impl="pallas",
                              kv_dtype="int8")
    src = _src(engine_mod)
    return [
        StepVariant(name="serve_paged_pallas:step", fn=eng._step_jit,
                    args=eng._example_args("step"),
                    donate_argnums=(1, 2, 4), mesh=None, source=src,
                    carry=lambda a, o: (a[0], o[0], o[1], a[3], o[2])),
        StepVariant(name="serve_paged_pallas:chunk", fn=eng._chunk_jit,
                    args=eng._example_args("chunk"),
                    donate_argnums=(1,), mesh=None, source=src,
                    carry=lambda a, o: (a[0], o[0]) + a[2:]),
    ]


def _build_zero1_fused() -> List[StepVariant]:
    """The fused packed ZeRO-1 update (parallel/zero1_fused.py): one
    reduce-scatter + one fused Adam kernel + one all-gather inside the
    shard_map — checked for the same donation/axis/retrace invariants
    as the composable zero1 step it accelerates."""
    import jax

    import jax.numpy as jnp

    from .. import mesh as mesh_lib
    from ..ops import logitcrossentropy
    from ..parallel import zero1_fused as zf
    from ..parallel.dp import flax_loss_fn
    from ..sharding import shard_batch

    mesh = mesh_lib.data_mesh(8)
    model, _ = _image_setup()
    x = jnp.zeros((16, 8, 8, 3), jnp.float32)
    y = jnp.zeros((16, 4), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x[:2], train=True)["params"]
    loss_fn = flax_loss_fn(model, logitcrossentropy, has_aux_state=False)
    state, _ = zf.zero1_fused_state(params, mesh)
    step = zf.make_train_step_zero1_fused(
        loss_fn, mesh, state, lr=1e-3, donate=True)
    batch = shard_batch({"image": x, "label": y}, mesh)
    return [StepVariant(
        name="zero1_fused", fn=step, args=(state, batch),
        donate_argnums=(0,), mesh=mesh, source=_src(zf),
        execute=True,
        carry=lambda args, out: (out[0], args[1]))]


#: name → builder; the six parallelism variants the acceptance gate
#: names (plus the explicit-collectives shard_map dp/zero1 pair the
#: comms ledger pins its signatures on), the serve engine's program
#: pools (dense and paged, the paged Pallas/int8 fast path) and the
#: fused ZeRO-1 update
VARIANT_BUILDERS: Dict[str, Callable[[], List[StepVariant]]] = {
    "dp": _build_dp,
    "dp_shardmap": _build_dp_shardmap,
    "zero1": _build_zero1,
    "zero1_shardmap": _build_zero1_shardmap,
    "zero1_fused": _build_zero1_fused,
    "fsdp": _build_fsdp,
    "tp": _build_tp,
    "layout_dp_fsdp": _build_layout_dp_fsdp,
    "layout_fsdp_tp": _build_layout_fsdp_tp,
    "layout_dp_fsdp_tp": _build_layout_dp_fsdp_tp,
    "pp_1f1b": _build_pp_1f1b,
    "pp_planned": _build_pp_planned,
    "pp_zb": _build_pp_zb,
    "context": _build_context,
    "serve": _build_serve,
    "serve_paged": _build_serve_paged,
    "serve_paged_pallas": _build_serve_paged_pallas,
}


def variant_names() -> List[str]:
    return list(VARIANT_BUILDERS)


def build_variants(names: Optional[Sequence[str]] = None) -> List[StepVariant]:
    """Build the named variants (default: all).  Unknown names raise —
    a typo in a CI invocation must not silently skip a variant."""
    out: List[StepVariant] = []
    for n in (names or variant_names()):
        if n not in VARIANT_BUILDERS:
            raise ValueError(
                f"unknown variant {n!r}; registered: {variant_names()}")
        out.extend(VARIANT_BUILDERS[n]())
    return out
