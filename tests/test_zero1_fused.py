"""Fused packed ZeRO-1 update (parallel/zero1_fused.py) invariants.

The fusion changes HOW the update executes (one packed buffer, one
collective each way, one kernel), never WHAT it computes — so the bar
is parity with the composable GSPMD ZeRO-1 step on an f32 model to f32
rounding (the two sum the same 8 per-device gradients in an order each
compiler pass picks, so the last bit is XLA's, not ours), plus the
memory layout claim and kernel-impl agreement.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fluxdistributed_tpu.mesh as mesh_lib
from fluxdistributed_tpu import optim, sharding
from fluxdistributed_tpu.models import MLP
from fluxdistributed_tpu.ops import logitcrossentropy
from fluxdistributed_tpu.parallel import make_train_step_zero1, zero1_state
from fluxdistributed_tpu.parallel import zero1_fused as zf
from fluxdistributed_tpu.parallel.dp import flax_loss_fn

STEPS = 4


@pytest.fixture(scope="module")
def setup():
    mesh = mesh_lib.data_mesh(8)
    # odd feature sizes force real padding in the packed buffer
    model = MLP(features=(13, 10))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 6, 6, 3), jnp.float32)
    y = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 10), 10)
    params = model.init(jax.random.PRNGKey(0), x[:2], train=True)["params"]
    loss_fn = flax_loss_fn(model, logitcrossentropy, has_aux_state=False)
    batch = sharding.shard_batch({"image": x, "label": y}, mesh)
    return mesh, params, loss_fn, batch


def test_bitwise_parity_with_gspmd_zero1(setup):
    """Same losses and params after STEPS Adam steps, to a few f32 ulps:
    wrong gradients (a missing reduction) miss this by 1e-1, while the
    GSPMD all-reduce and the explicit psum_scatter are free to sum the
    8 shards in different orders."""
    mesh, params, loss_fn, batch = setup
    opt = optim.adam(1e-2)
    ref_state, sh = zero1_state(params, opt, mesh)
    ref_step = make_train_step_zero1(loss_fn, opt, mesh, sh, donate=False)
    ref_losses = []
    for _ in range(STEPS):
        ref_state, m = ref_step(ref_state, batch)
        ref_losses.append(float(m["loss"]))

    state, _ = zf.zero1_fused_state(params, mesh)
    step = zf.make_train_step_zero1_fused(
        loss_fn, mesh, state, lr=1e-2, donate=False)
    losses = []
    for _ in range(STEPS):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


def test_opt_state_sharded_eighth_and_donation(setup):
    """m/v live as flat f32 buffers, 1/8 per device; the donated step
    updates in place without error."""
    mesh, params, loss_fn, batch = setup
    state, _ = zf.zero1_fused_state(params, mesh)
    leaf = state.opt_state["m"]
    assert leaf.dtype == jnp.float32 and leaf.ndim == 1
    assert leaf.shape[0] % (8 * 1024) == 0  # whole tiles per shard
    assert leaf.addressable_shards[0].data.shape[0] == leaf.shape[0] // 8
    step = zf.make_train_step_zero1_fused(
        loss_fn, mesh, state, lr=1e-2, donate=True)
    state2, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    assert int(state2.step) == 1


def test_pack_unpack_roundtrip():
    tree = {
        "a": jnp.arange(13.0),
        "b": jnp.arange(12.0).reshape(3, 4),
        "frozen": None,
    }
    flat = zf.pack_tree(tree, 4)
    assert flat.shape[0] % (4 * 1024) == 0
    back = zf.unpack_tree(flat, tree)
    for k in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
        assert back[k].shape == tree[k].shape
    assert back["frozen"] is None
    # pad tail is zero (inert through Adam)
    np.testing.assert_array_equal(np.asarray(flat[25:]), 0.0)


def test_kernel_impls_agree():
    """The real Pallas kernel (interpreter) and the XLA rendering of
    the same chain produce the same update — and both match optim.adam
    applied to the flat buffer."""
    rng = np.random.default_rng(0)
    n = 2 * 1024
    p = jnp.asarray(rng.normal(size=n), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32) * 0.1
    m = jnp.asarray(rng.normal(size=n), jnp.float32) * 0.01
    v = jnp.abs(jnp.asarray(rng.normal(size=n), jnp.float32)) * 0.01
    outs = {}
    for impl in ("xla", "interpret"):
        outs[impl] = zf.fused_adam_update(p, g, m, v, 7, lr=3e-3, impl=impl)
    for a, b in zip(outs["xla"], outs["interpret"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # vs optim.adam on the same buffer: same math, but the standalone
    # expression may fuse FMAs differently — 1-ULP tolerance
    ref_p, (ref_m, ref_v) = optim.adam(3e-3).apply(p, g, (m, v), 7)
    for got, ref in zip(outs["xla"], (ref_p, ref_m, ref_v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-8)


def test_kernel_rejects_ragged_buffer():
    x = jnp.zeros((1000,), jnp.float32)
    with pytest.raises(ValueError, match="pack_tree"):
        zf.fused_adam_update(x, x, x, x, 0)


def test_kernel_covers_tail_when_block_does_not_divide():
    """block_rows not dividing the row count must not drop the tail
    (a dropped grid block would all-gather uninitialized memory into
    the params): every element updates, interpret == xla."""
    rng = np.random.default_rng(1)
    n = 3 * 1024  # 24 rows; block_rows=16 does not divide
    p = jnp.asarray(rng.normal(size=n), jnp.float32)
    g = jnp.full((n,), 0.25, jnp.float32)
    z = jnp.zeros((n,), jnp.float32)
    ref = zf.fused_adam_update(p, g, z, z, 0, lr=1e-2, impl="xla",
                               block_rows=16)
    out = zf.fused_adam_update(p, g, z, z, 0, lr=1e-2, impl="interpret",
                               block_rows=16)
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # nonzero grad everywhere → every m element moved off zero
    assert (np.asarray(out[1]) != 0).all()


@pytest.mark.slow
def test_lr_schedule_rides_as_data(setup):
    """A schedule changes eta per step without retracing (the scalars
    are data): parity with the GSPMD variant under the same schedule."""
    mesh, params, loss_fn, batch = setup
    sched = optim.step_decay(1e-2, 0.5, 2)
    opt = optim.adam(sched)
    ref_state, sh = zero1_state(params, opt, mesh)
    ref_step = make_train_step_zero1(loss_fn, opt, mesh, sh, donate=False)
    state, _ = zf.zero1_fused_state(params, mesh)
    step = zf.make_train_step_zero1_fused(
        loss_fn, mesh, state, lr=sched, donate=False)
    for _ in range(STEPS):
        ref_state, _ = ref_step(ref_state, batch)
        state, _ = step(state, batch)
    for a, b in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
