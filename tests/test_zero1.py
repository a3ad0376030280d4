"""ZeRO-1 weight-update sharding invariants, on the 8-device mesh.

The whole point of ZeRO-1 (arXiv:2004.13336) is that it changes WHERE
the optimizer update runs, never WHAT it computes: reduce-scatter the
gradients, update slice 1/N per device, all-gather the params.  So the
acceptance bar is step-for-step parity with plain DP — both the GSPMD
variant and the explicit-collectives shard_map variant, for adam and
momentum, over multiple steps — plus the memory claim asserted directly:
each device holds ~1/8 of the optimizer state (``addressable_shards``
accounting), and padding of non-divisible leaves round-trips exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluxdistributed_tpu import optim, sharding
from fluxdistributed_tpu.models import MLP, SimpleCNN
from fluxdistributed_tpu.ops import logitcrossentropy
from fluxdistributed_tpu.parallel import (
    TrainState,
    make_train_step,
    make_train_step_zero1,
    make_train_step_zero1_shardmap,
    zero1_state,
)
from fluxdistributed_tpu.parallel import zero1 as zero1_lib
from fluxdistributed_tpu.parallel.dp import flax_loss_fn

BATCH = 32
NCLASS = 10
STEPS = 5


@pytest.fixture(scope="module")
def setup():
    import fluxdistributed_tpu.mesh as mesh_lib

    mesh = mesh_lib.data_mesh(8)
    # odd feature sizes: flattened leaves NOT divisible by 8 exercise the
    # pad-to-multiple path on every layer
    model = MLP(features=(13, NCLASS))
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, 6, 6, 3), jnp.float32)
    y = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(2), (BATCH,), 0, NCLASS), NCLASS
    )
    params = model.init(jax.random.PRNGKey(0), x[:2], train=True)["params"]
    loss_fn = flax_loss_fn(model, logitcrossentropy, has_aux_state=False)
    return mesh, params, loss_fn, {"image": x, "label": y}


def _run_dp(loss_fn, opt, mesh, params, batch, steps=STEPS):
    step = make_train_step(loss_fn, opt, mesh, donate=False)
    state = TrainState.create(sharding.replicate(params, mesh), opt)
    b = sharding.shard_batch(batch, mesh)
    losses = []
    for _ in range(steps):
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return state, losses


@pytest.mark.parametrize("opt_name", ["adam", "momentum"])
def test_gspmd_parity_with_dp(setup, opt_name):
    """zero1 GSPMD params match plain DP after STEPS optimizer steps."""
    mesh, params, loss_fn, batch = setup
    opt = optim.adam(1e-2) if opt_name == "adam" else optim.momentum(0.05, 0.9)
    ref_state, ref_losses = _run_dp(loss_fn, opt, mesh, params, batch)

    state, sh = zero1_state(params, opt, mesh)
    step = make_train_step_zero1(loss_fn, opt, mesh, sh, donate=False)
    b = sharding.shard_batch(batch, mesh)
    losses = []
    for _ in range(STEPS):
        state, m = step(state, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    for a, b_ in zip(jax.tree.leaves(ref_state.params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("opt_name", ["adam", "momentum"])
def test_shardmap_parity_with_dp(setup, opt_name):
    """Explicit reduce-scatter/all-gather variant matches plain DP too."""
    mesh, params, loss_fn, batch = setup
    opt = optim.adam(1e-2) if opt_name == "adam" else optim.momentum(0.05, 0.9)
    ref_state, ref_losses = _run_dp(loss_fn, opt, mesh, params, batch)

    state, _ = zero1_state(params, opt, mesh)
    step = make_train_step_zero1_shardmap(loss_fn, opt, mesh, state, donate=False)
    b = sharding.shard_batch(batch, mesh)
    losses = []
    for _ in range(STEPS):
        state, m = step(state, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    for a, b_ in zip(jax.tree.leaves(ref_state.params), jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-5, atol=1e-6)


def test_optimizer_state_memory_is_sharded_eighth(setup):
    """Per-device optimizer-state bytes ≈ 1/8 of the replicated baseline
    (exactly 1/8 of the PADDED total, asserted via addressable-shard
    accounting), and every device holds the same amount."""
    mesh, params, loss_fn, batch = setup
    opt = optim.adam(1e-2)

    repl = TrainState.create(sharding.replicate(params, mesh), opt)
    base = zero1_lib.per_device_state_bytes(repl.opt_state)

    state, _ = zero1_state(params, opt, mesh)
    got = zero1_lib.per_device_state_bytes(state.opt_state)

    assert set(got) == set(base) and len(got) == 8
    assert len(set(got.values())) == 1, "ZeRO-1 split must be even"
    per_dev = next(iter(got.values()))
    base_per_dev = next(iter(base.values()))
    # padded total / 8: with the MLP's odd leaves the padding overhead is
    # tiny, so per-device lands between exactly-1/8 and 1/7 of replicated
    assert base_per_dev / 8 <= per_dev < base_per_dev / 7, (per_dev, base_per_dev)

    # and params stay replicated (full copy per device) — ZeRO-1, not -3
    p_leaf = jax.tree.leaves(state.params)[0]
    assert p_leaf.addressable_shards[0].data.shape == p_leaf.shape


def test_padding_roundtrip_non_divisible_leaves():
    """_flatten_tree pads to a multiple of N; _unflatten_like restores
    the exact original values and shapes; pad entries stay zero through
    an optimizer update with zero grads."""
    tree = {
        "a": jnp.arange(13.0),            # 13 -> pad 3
        "b": jnp.arange(12.0).reshape(3, 4),  # 12 -> pad 4
        "c": jnp.ones((8,)),              # already divisible
        "frozen": None,
    }
    flat = zero1_lib._flatten_tree(tree, 8)
    assert flat["a"].shape == (16,) and flat["b"].shape == (16,)
    assert flat["c"].shape == (8,) and flat["frozen"] is None
    np.testing.assert_array_equal(np.asarray(flat["a"][13:]), 0.0)
    back = zero1_lib._unflatten_like(flat, tree)
    for k in ("a", "b", "c"):
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(tree[k]))
        assert back[k].shape == tree[k].shape

    # momentum on flat leaves: zero grads leave the padded tail at zero
    opt = optim.momentum(0.1, 0.9)
    st = opt.init(flat)
    newp, newst = opt.apply(flat, jax.tree.map(jnp.zeros_like, flat), st, 0)
    np.testing.assert_array_equal(np.asarray(newp["a"]), np.asarray(flat["a"]))
    np.testing.assert_array_equal(np.asarray(newst["a"]), 0.0)


def test_checkpoint_roundtrip_sharded_opt_state(setup, tmp_path):
    """Save a ZeRO-1 state (sharded flat optimizer leaves), restore onto
    a freshly prepared task, and keep training: restored state equals the
    saved one leaf-for-leaf and restores SHARDED (no gather on load)."""
    from fluxdistributed_tpu.train import load_checkpoint, save_checkpoint

    mesh, params, loss_fn, batch = setup
    opt = optim.adam(1e-2)
    state, sh = zero1_state(params, opt, mesh)
    step = make_train_step_zero1(loss_fn, opt, mesh, sh, donate=False)
    b = sharding.shard_batch(batch, mesh)
    for _ in range(3):
        state, _ = step(state, b)
    save_checkpoint(state, str(tmp_path), 3)

    # fresh task (as a resume would build it), then restore onto it
    fresh, _ = zero1_state(params, opt, mesh)
    restored = load_checkpoint(str(tmp_path), fresh, mesh=mesh)
    assert int(restored.step) == 3
    for a, b_ in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    # sharding preserved: each device holds a 1/8 slice, not a full copy
    leaf = jax.tree.leaves(restored.opt_state)[0]
    assert leaf.addressable_shards[0].data.shape[0] == leaf.shape[0] // 8

    # training continues from the restored state and stays in lockstep
    # with the uninterrupted run
    cont, _ = step(restored, b)
    ref, _ = step(state, b)
    for a, b_ in zip(jax.tree.leaves(ref.params), jax.tree.leaves(cont.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-6, atol=1e-7)


def test_trainer_wiring_and_model_state(tmp_path):
    """prepare_training(spmd='dp', zero1=True) runs end-to-end (BatchNorm
    model state included) and matches the zero1=False trainer path."""
    import fluxdistributed_tpu.mesh as mesh_lib
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.train import prepare_training, train
    from fluxdistributed_tpu.train.logging import NullLogger

    mesh = mesh_lib.data_mesh(8)
    ds = SyntheticDataset(nsamples=64, nclasses=NCLASS, shape=(8, 8, 3))

    def make(zero1):
        task = prepare_training(
            SimpleCNN(num_classes=NCLASS), ds, optim.momentum(0.05, 0.9),
            mesh=mesh, batch_size=16, cycles=3, seed=7, spmd="dp", zero1=zero1,
        )
        train(task, print_every=0, eval_every=0, logger=NullLogger())
        return task

    t_ref, t_z1 = make(False), make(True)
    assert int(t_z1.state.step) == 3
    for a, b in zip(
        jax.tree.leaves(t_ref.state.params), jax.tree.leaves(t_z1.state.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_zero1_rejects_non_dp_modes():
    from fluxdistributed_tpu.train import prepare_training

    with pytest.raises(ValueError, match="zero1"):
        prepare_training(
            SimpleCNN(num_classes=2), None, optim.adam(1e-3),
            layout="fsdp", zero1=True,
        )


def test_ema_shadow_roundtrip(setup):
    """with_ema under ZeRO-1: the shadow trains flat-sharded;
    zero1_ema_params restores model-shaped EMA params usable for eval."""
    mesh, params, loss_fn, batch = setup
    opt = optim.with_ema(optim.adam(1e-2), decay=0.9)
    state, sh = zero1_state(params, opt, mesh)
    step = make_train_step_zero1(loss_fn, opt, mesh, sh, donate=False)
    b = sharding.shard_batch(batch, mesh)
    for _ in range(3):
        state, _ = step(state, b)
    ema = zero1_lib.zero1_ema_params(state)
    for p, e in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(ema)
    ):
        assert p.shape == e.shape
        # warmup-corrected decay: after 3 steps the shadow tracks the
        # live params closely but is not identical
        assert not np.array_equal(np.asarray(p), np.asarray(e))
    # the shadow drives a forward pass at model shapes
    loss, _ = loss_fn(ema, {}, batch, False)
    assert np.isfinite(float(loss))


def test_shardmap_variant_rejects_norm_based_rules(setup):
    """LARS / global-norm clipping need cross-slice reductions the
    slice-local shard_map update cannot do — actionable error."""
    mesh, params, loss_fn, batch = setup
    state, _ = zero1_state(params, optim.lars(0.1), mesh)
    with pytest.raises(ValueError, match="GSPMD"):
        make_train_step_zero1_shardmap(loss_fn, optim.lars(0.1), mesh, state)


def test_gspmd_composes_with_accum_and_device_loop(setup):
    """accum_steps and steps_per_call ride the zero1 step unchanged:
    2 microbatch-accumulated steps x scan-2 == 2 plain zero1 steps on the
    equivalent batches (mean-loss semantics)."""
    mesh, params, loss_fn, batch = setup
    opt = optim.momentum(0.05, 0.9)
    b = sharding.shard_batch(batch, mesh)

    state, sh = zero1_state(params, opt, mesh)
    plain = make_train_step_zero1(loss_fn, opt, mesh, sh, donate=False)
    s_ref = state
    for _ in range(2):
        s_ref, _ = plain(s_ref, b)

    # accum: same global batch split into 2 microbatches
    accum = make_train_step_zero1(
        loss_fn, opt, mesh, sh, donate=False, accum_steps=2
    )
    s_acc, _ = accum(state, b)
    s_acc, _ = accum(s_acc, b)
    for a, b_ in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_acc.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-5)

    # device loop: 2 steps per dispatch on the stacked [2, batch, ...] item
    chunked = make_train_step_zero1(
        loss_fn, opt, mesh, sh, donate=False, steps_per_call=2
    )
    stacked = jax.tree.map(lambda x: jnp.stack([x, x]), b)
    s_chunk, m = chunked(state, stacked)
    assert m["loss"].shape == (2,)
    for a, b_ in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_chunk.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-5)
