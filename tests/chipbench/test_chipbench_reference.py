"""The plain references, the pool and the reference optimizers against
the package at a small size on the CPU, same seeded weights: a wrong
reference is found here and not with chip time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fluxdistributed_tpu as fd
from chipbench import refcommon, reference
from chipbench.pool import PoolDataset
from chipbench_tiny import harness, tiny_config


def _cell(name):
    cell = harness.load_cell(name)
    return tiny_config(cell.config), cell.ref


def _program_loss(cfg, model, params, mstate, images, labels):
    loss_fn = fd.flax_loss_fn(model, fd.logitcrossentropy)
    batch = {"image": images, "label": jax.nn.one_hot(labels, cfg["num_classes"])}

    def f(p):
        loss, (new, logits) = loss_fn(p, mstate, batch, True)
        return loss, (new, logits)
    return jax.value_and_grad(f, has_aux=True)(params)


@pytest.mark.parametrize("name", ["resnet50_b256_x1", "vit_l16_b32_x1"])
def test_reference_matches_the_package_model_in_float32(name):
    cfg, ref = _cell(name)
    params, mstate = ref.make_params(cfg, jax.random.PRNGKey(3))
    images = jax.random.normal(jax.random.PRNGKey(4), (8, *cfg["image"]))
    labels = jnp.arange(8) % cfg["num_classes"]
    model = getattr(fd.models, cfg["model"]["factory"])(
        **dict(cfg["model"]["kwargs"], dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        (loss, (new, logits)), grads = jax.jit(
            lambda p: _program_loss(cfg, model, p, mstate, images, labels))(params)
    prec = refcommon.Precision("f32")

    def lossf(p):
        lg, st = ref.forward(cfg, prec, p, mstate, images)
        return refcommon.cross_entropy_sum(lg, labels) / 8, (st, lg)
    (rloss, (rnew, rlogits)), rgrads = jax.jit(
        jax.value_and_grad(lossf, has_aux=True))(params)
    assert float(jnp.max(jnp.abs(logits - rlogits))) < 5e-3
    assert float(abs(loss - rloss)) < 1e-3
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(rnew)):
        np.testing.assert_allclose(a, b, atol=1e-3)
    names = reference.leaf_names(grads)
    gp = dict(zip(names, map(float, reference.leaf_norms(grads))))
    gr = dict(zip(names, map(float, reference.leaf_norms(rgrads))))
    assert reference.worst_gap(gp, gr) < 2e-2


@pytest.mark.parametrize("mode,least", [("bf16", 1e-4), ("fp8", 1e-3)])
def test_lower_precisions_move_the_logits(mode, least):
    cfg, ref = _cell("vit_l16_b32_x1")
    params, mstate = ref.make_params(cfg, jax.random.PRNGKey(3))
    images = jax.random.normal(jax.random.PRNGKey(4), (4, *cfg["image"]))
    full, _ = ref.forward(cfg, refcommon.Precision("f32"), params, mstate, images)
    low, _ = ref.forward(cfg, refcommon.Precision(mode), params, mstate, images)
    assert float(jnp.max(jnp.abs(full - low))) > least
    with pytest.raises(ValueError):
        refcommon.Precision("int4")


@pytest.mark.parametrize("name,hp", [
    ("momentum", {"lr": 0.1, "rho": 0.9}),
    ("adamw", {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
               "weight_decay": 0.1})])
def test_reference_optimizers_match_the_package(name, hp):
    init, step, first_grad = refcommon.OPTIMIZERS[name]
    opt = getattr(fd.optim, name)(**hp)
    params = {"a": {"w": jnp.linspace(-1, 1, 12).reshape(3, 4)}, "b": jnp.ones(5)}
    p1, s1, p2, s2 = params, opt.init(params), params, init(params)
    for k in range(3):
        grads = jax.tree.map(lambda x: jnp.cos(x * (k + 1)), params)
        p1, s1 = opt.apply(p1, grads, s1, k)
        p2, s2 = step(hp, p2, grads, s2, k)
        if k == 0:  # the first gradient, worked out from the program's state
            got = first_grad(hp, s1)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(grads)):
                np.testing.assert_allclose(a, b, rtol=1e-5)
    for a, b in zip(jax.tree.leaves((p1, s1)), jax.tree.leaves((p2, s2))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_pool_is_seeded_and_a_batch_holds_no_row_twice():
    a = PoolDataset(7, 32, (4, 4, 3), 10)
    b = PoolDataset(7, 32, (4, 4, 3), 10)
    c = PoolDataset(2 ** 31 + 5, 32, (4, 4, 3), 10)
    assert np.array_equal(a.images, b.images) and not np.array_equal(a.images, c.images)
    assert a.images.dtype == np.float32 and a.labels.dtype == np.int32
    imgs, labels = a.batch(np.random.default_rng(0), 32)
    rows = a.rows_of(imgs)
    assert sorted(rows.tolist()) == list(range(32))
    assert np.array_equal(labels, a.labels[rows])
    imgs2, _ = a.batch(None, 3, indices=[5, 1, 5])
    assert a.rows_of(imgs2).tolist() == [5, 1, 5]
    assert a.rows_of(np.zeros((1, 4, 4, 3), np.float32)).tolist() == [-1]
    with pytest.raises(ValueError):
        a.batch(np.random.default_rng(0), 33)


def test_fed_rows_counts_what_is_not_the_pool():
    pool = PoolDataset(1, 16, (4, 4, 3), 10)
    imgs, labels = pool.batch(np.random.default_rng(1), 8)
    onehot = np.eye(10, dtype=np.float32)[labels]
    assert harness.fed_rows(pool, [(imgs, onehot)])[1] == 0
    bad = imgs.copy()
    bad[2, 1, 1, 1] += 1.0          # a pixel altered on the way
    twice = imgs.copy()
    twice[3] = twice[4]             # a row fed twice
    wrong = onehot.copy()
    wrong[0] = np.roll(wrong[0], 1)  # another label
    assert harness.fed_rows(pool, [(bad, onehot)])[1] == 1
    assert harness.fed_rows(pool, [(twice, onehot)])[1] >= 1
    assert harness.fed_rows(pool, [(imgs, wrong)])[1] == 1


def test_worst_gap_and_live_leaves():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    # c is measured against the median leaf, not against itself
    assert reference.worst_gap(prog, ref) == pytest.approx(0.1)
    assert reference.live_leaves(ref) == {"a", "b"}
    unchanged = {k: 0.0 for k in ref}
    assert reference.worst_gap(unchanged, ref) == pytest.approx(1.0)
    assert reference.median_gap(unchanged, ref) == pytest.approx(1.0)
    assert reference.median_gap(prog, ref) == pytest.approx(1e-9)
    ok, table = reference.judge({"x": 0.1, "y": float("nan")}, {"x": 0.2, "y": 1.0})
    assert not ok and table["x"] == {"value": 0.1, "limit": 0.2}
    assert reference.judge({"x": 0.1}, {"x": 0.2})[0]
    # a number without a limit is not compared; a limit without its number fails
    ok, table = reference.judge({"x": 0.1, "z": 9.0}, {"x": 0.2})
    assert ok and "z" not in table
    assert not reference.judge({"x": 0.1}, {"x": 0.2, "y": 1.0})[0]
    assert not reference.judge({"x": 0.3}, {"x": 0.2})[0]
    assert not reference.judge({"x": 0.1}, {})[0]
