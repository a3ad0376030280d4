"""Rematerialization (jax.checkpoint) parity across model families.

``remat=True`` must be a pure memory/FLOPs trade: loss, gradients, and
mutable state bit-match the non-remat model, and parameter paths are
unchanged (the remat wrapper must not rename flax scopes — that would
orphan checkpoints and imported torch weights).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fluxdistributed_tpu as fd
from fluxdistributed_tpu import models
from fluxdistributed_tpu.models import convnext_test, lm_tiny, resnet18, vit_tiny
from fluxdistributed_tpu.models import lm_loss_fn
from fluxdistributed_tpu.ops import attention_core
from fluxdistributed_tpu.ops import pallas_attention as pa
from fluxdistributed_tpu.parallel.dp import flax_loss_fn

# tier-2 (slow): bit-level grad parity across remat'd full models — the tier-1 iteration loop must fit the
# 870s verify window (ROADMAP); CI's slow job still runs these.  What a rematerialised block keeps of its
# flash call (`test_a_rematerialised_block_keeps_its_flash_call`) is tier-1.
slow = pytest.mark.slow


def _grad_parity(m0, mr, loss_of, params):
    (l0, aux0), g0 = jax.value_and_grad(lambda p: loss_of(m0, p), has_aux=True)(params)
    (l1, aux1), g1 = jax.value_and_grad(lambda p: loss_of(mr, p), has_aux=True)(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for pa, a, b in zip(
        [k for k, _ in jax.tree_util.tree_leaves_with_path(g0)],
        jax.tree.leaves(g0),
        jax.tree.leaves(g1),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(pa)}",
        )
    return aux0, aux1


@slow
@pytest.mark.parametrize("family", ["resnet", "vit", "convnext"])
def test_image_model_remat_parity(family):
    mk = {
        "resnet": lambda **kw: resnet18(num_classes=10, dtype=jnp.float32, **kw),
        "vit": lambda **kw: vit_tiny(num_classes=10, dtype=jnp.float32, **kw),
        "convnext": lambda **kw: convnext_test(num_classes=10, dtype=jnp.float32, **kw),
    }[family]
    m0, mr = mk(), mk(remat=True)
    x = np.random.default_rng(0).normal(0, 1, (4, 32, 32, 3)).astype(np.float32)
    y = np.asarray(fd.onehot(np.arange(4) % 10, 10))
    variables = m0.init(jax.random.PRNGKey(0), x[:1], train=True)
    params = variables["params"]
    mstate = {k: v for k, v in variables.items() if k != "params"}

    # identical param paths: remat must not rename scopes
    vr = mr.init(jax.random.PRNGKey(0), x[:1], train=True)
    assert jax.tree_util.tree_structure(variables["params"]) == \
        jax.tree_util.tree_structure(vr["params"])

    def loss_of(model, p):
        loss, (ms, _) = flax_loss_fn(model, fd.logitcrossentropy)(
            p, mstate, {"image": x, "label": y}, True
        )
        return loss, ms

    ms0, ms1 = _grad_parity(m0, mr, loss_of, params)
    for a, b in zip(jax.tree.leaves(ms0), jax.tree.leaves(ms1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@slow
def test_lm_remat_parity():
    m0 = lm_tiny(vocab=32, dtype=jnp.float32)
    mr = lm_tiny(vocab=32, dtype=jnp.float32, remat=True)
    toks = np.random.default_rng(1).integers(0, 32, (4, 16)).astype(np.int32)
    params = m0.init(jax.random.PRNGKey(0), toks, train=False)["params"]

    def loss_of(model, p):
        loss, (ms, _) = lm_loss_fn(model)(p, {}, {"tokens": toks}, True)
        return loss, ms

    _grad_parity(m0, mr, loss_of, params)


@slow
def test_lm_remat_decode_unaffected():
    """decode=True ignores remat (no backward pass at inference; the
    cache write must not go through a checkpoint boundary)."""
    from fluxdistributed_tpu.models import generate

    mr = lm_tiny(vocab=32, dtype=jnp.float32, decode=True, remat=True)
    m0 = lm_tiny(vocab=32, dtype=jnp.float32, decode=True)
    toks = np.asarray([[3, 7]], np.int32)
    params = lm_tiny(vocab=32, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), toks, train=False
    )["params"]
    out_r = np.asarray(generate(mr, params, toks, total_len=6))
    out_0 = np.asarray(generate(m0, params, toks, total_len=6))
    np.testing.assert_array_equal(out_r, out_0)


# -- what a rematerialised block keeps of its flash call ----------------------

def _tiny_glm4(**kw):
    return models.glm4_moe_lite(
        vocab=64, dim=32, num_layers=2, num_heads=2, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=64, moe_intermediate_size=16, n_routed_experts=8,
        experts_held=[0, 4], num_experts_per_tok=2,
        num_nextn_predict_layers=1, dtype="float32",
        attention_impl="pallas", attn_block_q=8, attn_block_k=8, **kw)


def _tiny_lfm2(**kw):
    return models.lfm2_moe(
        vocab=64, dim=32, num_layers=3, num_heads=8, num_kv_heads=2,
        layer_types=["conv", "full_attention", "full_attention"],
        intermediate_size=64, moe_intermediate_size=16, n_routed_experts=8,
        experts_held=[0, 4], num_experts_per_tok=2, num_dense_layers=1,
        dtype="float32", attention_impl="pallas", attn_block_q=8,
        attn_block_k=8, **kw)


def _tiny_lm(**kw):
    return lm_tiny(vocab=64, dtype=jnp.float32,
                   attn_fn=attention_core("flash", block=8), **kw)


@pytest.mark.parametrize("make", [_tiny_glm4, _tiny_lfm2, _tiny_lm],
                         ids=["glm4_moe_lite", "lfm2_moe", "transformer_lm"])
def test_a_rematerialised_block_keeps_its_flash_call(make, monkeypatch):
    """``remat=True`` saves what the flash forward call made (``out``,
    ``lse``) and nothing else: loss and every gradient leaf equal plain
    ``nn.remat``'s to the bit (the same kernel on the same operands,
    called once where plain remat calls it twice), and the model's
    without remat within this file's tolerances."""
    tokens = np.random.default_rng(2).integers(0, 64, (2, 16)).astype(np.int32)
    variables = make().init(jax.random.PRNGKey(0), tokens, train=True)
    params = variables["params"]
    state = {k: v for k, v in variables.items()
             if k not in ("params", "losses")}

    def run(model):
        """The gradient's jaxpr as text, and (loss, gradients): one trace."""
        traced = jax.jit(jax.value_and_grad(lambda p: lm_loss_fn(model)(
            p, state, {"tokens": tokens}, True)[0])).trace(params)
        return str(traced.jaxpr), traced.lower().compile()(params)

    kept_text, (kept_loss, kept) = run(make(remat=True))
    for name in (pa.KEPT_OUT, pa.KEPT_LSE):
        assert f"name={name}" in kept_text, name
    _, (loss, grads) = run(make())
    with monkeypatch.context() as m:  # maybe_remat without its policy
        m.setattr(jax.checkpoint_policies, "save_only_these_names",
                  lambda *names: None)
        plain_text, (plain_loss, plain) = run(make(remat=True))
    # plain remat runs the forward kernel again in the backward pass
    calls = lambda t: t.count(f"name={pa.KERNEL_NAMES[0]}")  # noqa: E731
    assert calls(plain_text) > calls(kept_text)

    assert float(kept_loss) == float(plain_loss)
    np.testing.assert_allclose(float(kept_loss), float(loss), rtol=1e-6)
    for (path, a), b, c in zip(jax.tree_util.tree_leaves_with_path(kept),
                               jax.tree.leaves(plain), jax.tree.leaves(grads)):
        where = jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), where)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5,
                                   atol=1e-6, err_msg=where)
