"""Tensor parallelism: TP-sharded training == replicated training.

The reference's invariant (distributed == single-device,
test/single_device.jl:115-168) applied to the model axis: a ViT trained
with Megatron-sharded params under ``Layout("tp", dp=2, tp=4)`` must
produce the same losses and parameters as the plain replicated DP step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fluxdistributed_tpu as fd
from fluxdistributed_tpu import optim, sharding
from fluxdistributed_tpu.mesh import make_mesh
from fluxdistributed_tpu.models import vit_tiny
from fluxdistributed_tpu.parallel import Layout, TrainState, make_train_step, rules
from fluxdistributed_tpu.parallel.dp import flax_loss_fn
from jax.sharding import PartitionSpec as P

from _layout_step import layout_step

TP = Layout("tp", dp=2, tp=4)


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh({"data": 2, "model": 4})
    model = vit_tiny(num_classes=10, dtype=jnp.float32, dropout=0.0)
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (16, 32, 32, 3)).astype(np.float32)
    y = np.asarray(fd.onehot(rng.integers(0, 10, 16), 10))
    variables = model.init(jax.random.PRNGKey(0), x[:1], train=False)
    loss_fn = flax_loss_fn(model, fd.logitcrossentropy)
    opt = optim.momentum(0.1, 0.9)
    return mesh, model, loss_fn, opt, variables["params"], {"image": x, "label": y}


def test_specs_cover_attention_and_mlp(setup):
    _, _, _, _, params, _ = setup
    specs = rules.match_partition_rules(rules.vit_tp_rules_table(), params)
    flat = {
        "/".join(str(k.key) for k in kp): s
        for kp, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }
    assert flat["block0/MultiHeadAttention_0/qkv/kernel"] == P(None, None, "model", None)
    assert flat["block0/MultiHeadAttention_0/out/kernel"] == P("model", None, None)
    assert flat["block0/MlpBlock_0/Dense_0/kernel"] == P(None, "model")
    assert flat["block0/MlpBlock_0/Dense_1/kernel"] == P("model", None)
    assert flat["head/kernel"] == P()


def test_broadcast_prefix_handles_adam_tuples(setup):
    _, _, _, _, params, _ = setup
    opt = optim.adam(1e-3)
    st = opt.init(params)
    specs = rules.match_partition_rules(rules.vit_tp_rules_table(), params)
    st_specs = rules.broadcast_prefix(specs, st)
    # The qkv kernel's (m, v) tuple must both carry the qkv spec.
    got = st_specs["block0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    assert got == (P(None, None, "model", None), P(None, None, "model", None))


def test_tp_matches_dp(setup):
    mesh, model, loss_fn, opt, params, batch = setup

    # Replicated DP baseline on the same mesh (model axis unused).
    state0 = TrainState.create(sharding.replicate(params, mesh), opt)
    dp_step = make_train_step(loss_fn, opt, mesh, donate=False)
    b = sharding.shard_batch(batch, mesh)

    dp_state, m_dp = dp_step(state0, b)
    dp_state, m_dp2 = dp_step(dp_state, b)

    # TP: same initial params, Megatron shardings.
    tp_mesh, tp_state, tp_step = layout_step(model, params, opt, loss_fn, TP)
    tp_b = sharding.shard_batch(batch, tp_mesh, axis=TP.batch_axes)
    tp_state, m_tp = tp_step(tp_state, tp_b)
    tp_state, m_tp2 = tp_step(tp_state, tp_b)

    np.testing.assert_allclose(float(m_tp["loss"]), float(m_dp["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(m_tp2["loss"]), float(m_dp2["loss"]), rtol=1e-5)
    for a, b_ in zip(jax.tree.leaves(dp_state.params), jax.tree.leaves(tp_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4)


def test_donated_state_does_not_delete_source_params(setup):
    """replicate/layout.shard_state must copy: donating the state into
    the compiled step would otherwise delete the caller's original arrays
    (device_put is zero-copy on shared devices)."""
    mesh, model, loss_fn, opt, params, batch = setup
    state = TrainState.create(sharding.replicate(params, mesh), opt)
    step = make_train_step(loss_fn, opt, mesh, donate=True)
    b = sharding.shard_batch(batch, mesh)
    state, _ = step(state, b)  # donates the pre-step state buffers
    # Source params must still be alive and usable, also after a
    # layout-placed state made from them was donated.
    tp_mesh, tp_state, tp_step = layout_step(
        model, params, opt, loss_fn, TP, donate=True)
    tp_step(tp_state, sharding.shard_batch(batch, tp_mesh, axis=TP.batch_axes))
    _, tp_state, _ = layout_step(model, params, opt, loss_fn, TP)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(tp_state.params))


def test_tp_params_actually_sharded(setup):
    mesh, model, loss_fn, opt, params, batch = setup
    _, tp_state, _ = layout_step(model, params, opt, loss_fn, TP)
    qkv = tp_state.params["block0"]["MultiHeadAttention_0"]["qkv"]["kernel"]
    assert "model" in qkv.sharding.spec
    # Each device holds 1/4 of the heads.
    shard_shape = qkv.sharding.shard_shape(qkv.shape)
    assert shard_shape[2] == qkv.shape[2] // 4
