"""FSDP (ZeRO-3 via GSPMD) invariants, on the 8-device mesh, through
the ``fsdp`` / ``fsdp_tp`` layouts.

Sharding annotations must never change the math: the FSDP step's params
after N steps must match the replicated DP step's bit-for-bit behavior
(same tolerance as the DP-vs-single-device invariant the reference
asserts, test/single_device.jl:153-166).  And the point of FSDP — the
memory win — is asserted directly: each device holds ~1/8th of every
large leaf (``addressable_shards``), not a full copy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fluxdistributed_tpu import optim, sharding
from fluxdistributed_tpu.models import SimpleCNN
from fluxdistributed_tpu.ops import logitcrossentropy
from fluxdistributed_tpu.parallel import (
    Layout,
    TrainState,
    make_eval_step,
    make_train_step,
    rules,
)
from fluxdistributed_tpu.parallel.dp import flax_loss_fn

from _layout_step import layout_step

BATCH = 32
NCLASS = 10


@pytest.fixture(scope="module")
def setup():
    import fluxdistributed_tpu.mesh as mesh_lib

    mesh = mesh_lib.data_mesh(8)
    model = SimpleCNN(num_classes=NCLASS)
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, 8, 8, 3), jnp.float32)
    y = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(2), (BATCH,), 0, NCLASS), NCLASS
    )
    params = model.init(jax.random.PRNGKey(0), x[:2], train=True)["params"]
    loss_fn = flax_loss_fn(model, logitcrossentropy)
    return mesh, model, params, loss_fn, {"image": x, "label": y}


def test_leaf_spec_rule():
    # large 2D leaf: shard the larger dim; trailing wins ties
    assert rules.fsdp_leaf_spec((4096, 512), "data", 8) == P("data", None)
    assert rules.fsdp_leaf_spec((512, 4096), "data", 8) == P(None, "data")
    assert rules.fsdp_leaf_spec((4096, 4096), "data", 8) == P(None, "data")
    # conv HWIO: features dim, not the 3x3 window
    assert rules.fsdp_leaf_spec((3, 3, 256, 256), "data", 8) == P(
        None, None, None, "data"
    )
    # small leaves (BN scale etc.) stay replicated
    assert rules.fsdp_leaf_spec((64,), "data", 8) == P()
    # no divisible dim -> replicated
    assert rules.fsdp_leaf_spec((63, 65), "data", 8, min_size=1) == P()
    # scalars
    assert rules.fsdp_leaf_spec((), "data", 8) == P()


def test_fsdp_matches_dp(setup):
    mesh, model, params, loss_fn, batch = setup
    opt = optim.momentum(0.05, 0.9)
    b = sharding.shard_batch(batch, mesh)

    # replicated DP ground truth
    dp_state = TrainState.create(sharding.replicate(params, mesh), opt)
    dp_step = make_train_step(loss_fn, opt, mesh, donate=False)

    # FSDP: same initial params, sharded state (min_size=64: a small
    # model, force sharding)
    fs_mesh, fs_state, fs_step = layout_step(
        model, params, opt, loss_fn, "fsdp")
    fs_b = sharding.shard_batch(batch, fs_mesh, axis="fsdp")

    for _ in range(3):
        dp_state, dp_m = dp_step(dp_state, b)
        fs_state, fs_m = fs_step(fs_state, fs_b)
        np.testing.assert_allclose(
            np.asarray(dp_m["loss"]), np.asarray(fs_m["loss"]), rtol=1e-6
        )

    for (pa, a), (pb, bb) in zip(
        jax.tree_util.tree_leaves_with_path(dp_state.params),
        jax.tree_util.tree_leaves_with_path(fs_state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(bb), rtol=2e-5, atol=1e-6,
            err_msg=f"param mismatch at {jax.tree_util.keystr(pa)}",
        )


def test_fsdp_shards_memory(setup):
    mesh, model, params, loss_fn, batch = setup
    opt = optim.adam(1e-3)
    fs_mesh, state, _ = layout_step(model, params, opt, loss_fn, "fsdp")

    n = fs_mesh.shape["fsdp"]
    sharded = 0
    for leaf in jax.tree.leaves(state.params):
        spec = leaf.sharding.spec
        shard = leaf.addressable_shards[0].data
        if any(spec):  # the overlay pads a whole leaf's spec to its rank
            assert shard.size == leaf.size // n, (spec, leaf.shape, shard.shape)
            sharded += 1
        else:
            assert shard.size == leaf.size
    assert sharded > 0, "no leaf was sharded — rule or model shapes changed"
    # optimizer moments follow the same rule (same shapes), incl. adam's
    for leaf in jax.tree.leaves(state.opt_state):
        assert leaf.addressable_shards[0].data.size <= leaf.size


def test_fsdp_through_trainer():
    """The user path: prepare_training(layout='fsdp') → train → loss falls,
    and the trainer's state really is sharded."""
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.train import prepare_training, train
    from fluxdistributed_tpu.train.logging import NullLogger

    ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(8, 8, 3))
    task = prepare_training(
        SimpleCNN(num_classes=4), ds, optim.momentum(0.1, 0.9),
        batch_size=16, cycles=30, layout="fsdp",
    )
    n = task.mesh.shape["fsdp"]
    assert any(
        l.addressable_shards[0].data.size == l.size // n
        for l in jax.tree.leaves(task.state.params)
    ), "no trainer param leaf is sharded under layout='fsdp'"
    losses = []
    orig = task.step_fn

    def recording(state, batch):
        state, m = orig(state, batch)
        losses.append(float(m["loss"]))
        return state, m

    task.step_fn = recording
    train(task, print_every=0, eval_every=0, logger=NullLogger())
    assert losses[-1] < losses[0], (losses[0], losses[-1])


def test_fsdp_checkpoint_roundtrip(setup, tmp_path):
    """Save an FSDP-sharded state, restore onto the sharded target: values
    round-trip and the restored leaves keep their FSDP shardings (no
    silent gather-to-replicated on resume)."""
    from fluxdistributed_tpu.train.checkpoint import load_checkpoint, save_checkpoint

    mesh, model, params, loss_fn, batch = setup
    opt = optim.momentum(0.05, 0.9)
    mesh, state, step = layout_step(model, params, opt, loss_fn, "fsdp")
    b = sharding.shard_batch(batch, mesh, axis="fsdp")
    state, _ = step(state, b)

    save_checkpoint(state, str(tmp_path), 1)
    restored = load_checkpoint(str(tmp_path), state, mesh=mesh)

    n = mesh.shape["fsdp"]
    resharded = 0
    for old, new in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
        assert new.sharding == old.sharding
        if new.addressable_shards[0].data.size == new.size // n:
            resharded += 1
    assert resharded > 0
    # and the restored state steps
    st2, m = step(restored, b)
    assert np.isfinite(np.asarray(m["loss"]))


def test_hybrid_fsdp_tp_lm():
    """2-D sharding on (fsdp=2, model=4): TP rules + FSDP on the leftover
    dim → per-device shards ~1/8 of large leaves, numerics match DP."""
    import fluxdistributed_tpu.mesh as mesh_lib
    from fluxdistributed_tpu.models import lm_loss_fn, lm_tiny

    vocab = 32
    model = lm_tiny(vocab=vocab, dtype=jnp.float32)
    toks = np.random.default_rng(11).integers(0, vocab, (16, 24)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), toks[:2], train=False)["params"]
    opt = optim.momentum(0.05, 0.9)
    loss_fn = lm_loss_fn(model)

    mesh, hy_state, hy_step = layout_step(
        model, params, opt, loss_fn, Layout("fsdp_tp", fsdp=2, tp=4))
    # embedding: vocab over model (TP) + dim over fsdp (FSDP)
    assert hy_state.params["embed"]["embedding"].sharding.spec == P(
        "model", "fsdp")
    qkv_leaf = hy_state.params["block0"]["CausalSelfAttention_0"]["qkv"]["kernel"]
    assert qkv_leaf.sharding.spec == P("fsdp", None, "model", None)
    assert qkv_leaf.addressable_shards[0].data.size == qkv_leaf.size // 8
    b_hy = sharding.shard_batch({"tokens": toks}, mesh, axis="fsdp")

    dp_mesh = mesh_lib.data_mesh(8)
    dp_state = TrainState.create(sharding.replicate(params, dp_mesh), opt)
    dp_step = make_train_step(loss_fn, opt, dp_mesh, donate=False)
    b_dp = sharding.shard_batch({"tokens": toks}, dp_mesh)

    for _ in range(3):
        dp_state, dp_m = dp_step(dp_state, b_dp)
        hy_state, hy_m = hy_step(hy_state, b_hy)
        np.testing.assert_allclose(
            float(dp_m["loss"]), float(hy_m["loss"]), rtol=1e-5
        )


# slow tier: the trainer-layer fsdp x tp composition re-compiles the
# whole hybrid step; the parallel-layer hybrid (test_hybrid_fsdp_tp_lm)
# keeps the axis composition in tier-1 (870s window, ROADMAP)
@pytest.mark.slow
def test_fsdp_tp_through_trainer():
    """The user path for the hybrid 2-D recipe: prepare_training(
    layout=fsdp x tp) shards state over BOTH axes and training learns."""
    from fluxdistributed_tpu.data import SyntheticTextDataset
    from fluxdistributed_tpu.models import lm_loss_fn, lm_tiny
    from fluxdistributed_tpu.train import prepare_training, train
    from fluxdistributed_tpu.train.logging import NullLogger

    model = lm_tiny(vocab=32, dtype=jnp.float32)
    ds = SyntheticTextDataset(vocab=32, seqlen=32, peak=0.9)
    task = prepare_training(
        model, ds, optim.adam(3e-3), batch_size=32, cycles=30,
        loss_fn=lm_loss_fn(model), topk=(),
        layout=Layout("fsdp_tp", fsdp=2, tp=4),
    )
    emb = task.state.params["embed"]["embedding"]
    assert emb.sharding.spec == P("model", "fsdp")
    assert emb.addressable_shards[0].data.size == emb.size // 8
    losses = []
    orig = task.step_fn

    def rec(state, batch):
        out = orig(state, batch)
        losses.append(float(out[1]["loss"]))
        return out

    task.step_fn = rec
    train(task, print_every=0, eval_every=0, topk=(), logger=NullLogger())
    assert losses[-1] < losses[0]


def test_fsdp_eval_and_accum(setup):
    mesh, model, params, loss_fn, batch = setup
    opt = optim.momentum(0.05, 0.9)
    # grad accumulation composes with FSDP (scan over microbatches)
    mesh, state, step = layout_step(
        model, params, opt, loss_fn, "fsdp", accum_steps=2)
    b = sharding.shard_batch(batch, mesh, axis="fsdp")
    state2, m = step(state, b)
    assert np.isfinite(np.asarray(m["loss"]))

    # eval takes the sharded state directly (no gather, no resharding)
    ev = make_eval_step(
        loss_fn, mesh, axis=("data", "fsdp"), topk=(1,),
        state_shardings=jax.tree.map(lambda x: x.sharding, state))
    loss, metrics = ev(state2, b)
    assert np.isfinite(np.asarray(loss))
    assert 0.0 <= float(metrics["top1"]) <= 1.0
