"""Laguna-S-2.1 on the CPU at tiny sizes in float32: the model against the
plain reference (``chipbench/configs/laguna_s21.py``, which imports
nothing of the program) on its seeded weights, loss and every leaf's
gradient; the expert share; YaRN's frequencies and the partial rotary
against their closed form; the window; grouped-query attention at groups
of 9 and 6 with a head of 128 apart from dim / heads; the per-head gate;
and LFM2's attention and the rotary helper tracing as they did before
their new fields.

Tolerances: both sides compute in float32 (products at ``highest`` in
the reference), in another order of operations (the program sorts rows
and runs grouped products, and under the interpreter the flash kernels
keep an online softmax), so they differ by float32 rounding alone: the
model's leaves read under 2e-6 relative, a module's outputs under 1e-6.
``TOL`` 5e-5 is twenty-five times the first and a hundredth or less of
what a full layer without YaRN gives
(``test_the_tolerance_catches_...``).  Closed forms are held to float32's
own rounding, 1e-6."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

import fluxdistributed_tpu as fd
from fluxdistributed_tpu.models import laguna as lg
from fluxdistributed_tpu.models.experts import SwiGLU
from fluxdistributed_tpu.models.lfm2_moe import GroupedQueryAttention
from fluxdistributed_tpu.models.transformer_lm import YaRN, rope, yarn_inv_freq
from fluxdistributed_tpu.obs import get_registry
from fluxdistributed_tpu.ops.attention import dot_product_attention
from fluxdistributed_tpu.parallel import ep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, refcommon  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "laguna_s21.json")
REF = harness.load_module(os.path.splitext(CONFIG)[0] + ".py")
PREC = refcommon.Precision("f32")
TOL = 5e-5


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def cell_config():
    with open(CONFIG) as f:
        return json.load(f)


def tiny(impl="xla", offset=4, layers=4, held=(0, 4), t=32):
    """(reference's cfg, the program's factory keywords) at a tiny size:
    hidden 32, heads of 16 (6 in a full layer, 9 in a sliding one, over
    one key-value head: the cell's groups), a window of 16, 4 of 16
    experts a token, the published lists of kinds."""
    heads = [6 if i % 4 == 0 else 9 for i in range(48)]
    dense = [0] if offset == 0 else []
    base = cell_config()
    cfg = dict(base, hidden_size=32, head_dim=16, num_key_value_heads=1,
               num_attention_heads_per_layer=heads, intermediate_size=64,
               moe_intermediate_size=16, shared_expert_intermediate_size=16,
               router_experts=16, experts_held=list(held), num_experts_per_tok=4,
               sliding_window=16, num_hidden_layers=layers, layer_offset=offset,
               mlp_only_layers=dense, input={"kind": "tokens", "seq_len": t, "vocab": 64})
    kw = dict(base["model"]["kwargs"], vocab=64, dim=32, num_layers=layers,
              layer_offset=offset, num_heads_per_layer=heads, num_kv_heads=1,
              head_dim=16, intermediate_size=64, moe_intermediate_size=16,
              shared_expert_intermediate_size=16, n_routed_experts=16,
              experts_held=list(held), num_experts_per_tok=4, sliding_window=16,
              mlp_only_layers=dense, dtype="float32", attention_impl=impl,
              attn_block_q=16, attn_block_k=16, remat=impl == "pallas")
    return cfg, kw


def gaps(cfg, model_kw, seed=3):
    """The loss's gap and the worst leaf's gradient gap, program against
    reference, on the reference's seeded weights and router state and
    two rows."""
    model = fd.models.laguna_s21(**model_kw)
    params, state = REF.make_params(cfg, jax.random.PRNGKey(seed))
    t = cfg["input"]["seq_len"]
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, t), 0, 64)
    loss = fd.models.lm_loss_fn(model)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: loss(p, state, {"tokens": toks}, True)[0]))(params)
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: REF.row_loss_sum(cfg, PREC, p, state, toks)[0] / 2))(params)
    leaves = jax.tree.leaves(jax.tree.map(rel, g_got, g_want))
    assert len(leaves) == len(REF.param_shapes(cfg))
    return abs(float(got) - float(want)) / abs(float(want)), max(leaves)


@pytest.mark.parametrize("impl,offset,layers", [
    ("pallas", 4, 2), ("xla", 0, 2)], ids=["cut_4_5_kernels", "first_0_1_plain"])
def test_the_model_is_the_reference(impl, offset, layers):
    """The cell's first two layers (published 4 and 5: full and sliding,
    experts in each; 6 and 7 are 5's kind again) on the flash kernels,
    interpreted and rematerialised; and published layers 0-1 (full with
    the dense layer 0's SwiGLU, sliding with experts) on the plain
    path."""
    loss_gap, grad_gap = gaps(*tiny(impl, offset, layers))
    assert loss_gap < TOL and grad_gap < TOL, (loss_gap, grad_gap)


def test_the_tolerance_catches_plain_frequencies_in_the_full_layer(monkeypatch):
    """The full layer on the plain frequencies of its 64 rotary features
    (YaRN's factor 1 and no attention factor) in the program: the
    comparison fails by far (a sliding layer without its band is held by
    ``test_a_sliding_layer_forgets_keys_older_than_its_band``)."""
    monkeypatch.setattr(lg.LagunaConfig, "yarn", property(
        lambda c: YaRN(1.0, c.yarn_original_max_positions)))
    loss_gap, grad_gap = gaps(*tiny("xla"))
    assert grad_gap > 100 * TOL, (loss_gap, grad_gap)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Four chips' routed parts (4 of 16 experts each, routed over all
    16), with the shared expert and the routing counted once, are the
    uncut reference's expert layer; each share through the program is
    that share through the reference."""
    cfg, _ = tiny(held=(0, 16))
    params, state = REF.make_params(cfg, jax.random.PRNGKey(7))
    p, s = params["layer0"], state["router"]["layer0"]
    x = jax.random.normal(jax.random.PRNGKey(8), (64, 32), jnp.float32)
    want, _ = REF.expert_mlp(cfg, PREC, p, s, x)
    chosen, weights, load = ep.sigmoid_route(
        x, p["moe"]["router"], s["moe"]["bias"], top_k=4,
        scale=cfg["moe_routed_scaling_factor"])
    total = SwiGLU(16, jnp.float32).apply({"params": p["shared"]}, x)
    for first in range(0, 16, 4):
        part = {k: p["moe"][k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}
        mine = ep.held_experts_apply(x, chosen, weights, *part.values(), 16,
                                     first=first)
        share = dict(cfg, experts_held=[first, 4])
        assert rel(mine, REF.routed_part(share, PREC, part, x, chosen, weights)) < TOL
        total = total + mine
    assert rel(total, want) < TOL
    assert float(load.sum()) == 4 * len(x)


def test_yarn_and_the_partial_rotary_are_the_closed_form():
    """The cell's full layers: theta 5e5 over the 64 features that turn,
    the ramp from pair 9 (c(32) = 9.04, floored) to pair 18 (c(1) =
    17.49, ceiled), factor 128; cos and sin times 1.4852; the other 64
    features untouched.  The reference's frequencies are the same."""
    theta, dim, af = 5e5, 64, 1.4852030263919618
    c = lambda n: dim * math.log(8192 / (2 * math.pi * n)) / (2 * math.log(theta))  # noqa: E731
    assert (math.floor(c(32)), math.ceil(c(1))) == (9, 18)
    own = np.array([theta ** (-2 * i / dim) for i in range(dim // 2)])
    ramp = np.clip((np.arange(dim // 2) - 9) / 9, 0, 1)
    want = own * (1 - ramp) + own / 128 * ramp
    assert want[9] == own[9] and want[18] == own[18] / 128
    yarn = YaRN(128.0, 8192, 32.0, 1.0, af)
    np.testing.assert_allclose(yarn_inv_freq(dim, theta, yarn), want, rtol=1e-6)
    turn, inv, scale = REF.rotary(cell_config(), "full_attention")
    assert (turn, scale) == (dim, af)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    # a unit pair at every position turns by p * inv_i and grows by af
    t = 40
    x = jnp.zeros((1, t, 1, 128)).at[..., 0:dim:2].set(1.0)
    x = x.at[..., dim:].set(jax.random.normal(jax.random.PRNGKey(0), (1, t, 1, 64)))
    out = rope(x, jnp.arange(t), theta, rotary_dim=dim, yarn=yarn)[0, :, 0]
    ang = np.arange(t)[:, None] * want
    np.testing.assert_allclose(out[:, 0:dim:2], af * np.cos(ang), atol=1e-5)
    np.testing.assert_allclose(out[:, 1:dim:2], af * np.sin(ang), atol=1e-5)
    np.testing.assert_array_equal(out[:, dim:], x[0, :, 0, dim:])
    # the sliding layers: plain frequencies on all 128, no factor
    turn, inv, scale = REF.rotary(cell_config(), "sliding_attention")
    assert (turn, scale) == (128, 1.0)
    np.testing.assert_allclose(inv, 1e4 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6)


def attention_module(impl, h=6, window=None, head_dim=16, gate=True, full=False):
    cfg = lg.LagunaConfig(vocab=64, dim=32, head_dim=head_dim, num_kv_heads=1)
    return GroupedQueryAttention(
        h, 1, rope_theta=5e5 if full else 1e4, dtype=jnp.float32,
        attention_impl=impl, block_q=16, block_k=16, head_dim=head_dim,
        qk_norm=False, window=window, rotary_dim=cfg.rotary_dim if full else None,
        yarn=cfg.yarn if full else None, head_gate=gate)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_sliding_layer_forgets_keys_older_than_its_band(impl):
    """Inputs at positions 0-19 changed: with a window of 16, outputs
    from position 35 on stay as they were, to the bit (the band ends at
    20 there), and 20-34 move; a full layer moves everywhere after 19."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32))
    y = x.at[:, :20].add(jax.random.normal(jax.random.PRNGKey(2), (1, 20, 32)))
    sliding = attention_module(impl, window=16)
    p = sliding.init(jax.random.PRNGKey(3), x)
    a, b = sliding.apply(p, x), sliding.apply(p, y)
    np.testing.assert_array_equal(a[:, 35:], b[:, 35:])
    assert bool(jnp.all(jnp.any(a[:, 20:35] != b[:, 20:35], axis=-1)))
    full = attention_module(impl, full=True)
    a, b = full.apply(p, x), full.apply(p, y)
    assert bool(jnp.all(jnp.any(a[:, 20:] != b[:, 20:], axis=-1)))


@pytest.mark.parametrize("kind,h", [("sliding_attention", 9), ("full_attention", 6)])
def test_grouped_query_attention_at_groups_of_9_and_6_with_a_head_of_128(kind, h):
    """9 and 6 query heads over one key-value head of 128 features, a
    hidden size of 64 (so a head is not dim / heads), on the flash
    kernels (interpreted): forward and the gradients of the weights and
    the input, against the reference."""
    impl = "pallas"
    full = kind == "full_attention"
    heads = [h] * 48
    cfg = dict(cell_config(), hidden_size=64, head_dim=128, num_key_value_heads=1,
               num_attention_heads_per_layer=heads, sliding_window=16,
               layer_types=[kind] * 48)
    module = attention_module(impl, h, None if full else 16, 128, full=full)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 64))
    params = module.init(jax.random.PRNGKey(5), x)["params"]
    assert params["q"]["kernel"].shape == (64, h, 128)
    assert params["out"]["kernel"].shape == (h, 128, 64)
    w = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    prog = lambda p, x: jnp.sum(module.apply({"params": p}, x) * w)  # noqa: E731
    ref = lambda p, x: jnp.sum(REF.attention(cfg, PREC, p, x[0], 4) * w[0])  # noqa: E731
    got, want = (jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, x)
                 for f in (prog, ref))
    assert abs(got[0] - want[0]) < TOL * abs(want[0]) + 1e-4
    for g, r in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        assert rel(g, r) < TOL


def test_the_gate_is_per_head():
    """With ``W_o`` reading head 2 alone, the output moves with the gate's
    column 2 and with no other column; the gate is ``[dim, heads]``."""
    module = attention_module("xla")
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 32, 32))
    p = module.init(jax.random.PRNGKey(8), x)["params"]
    assert p["gate"]["kernel"].shape == (32, 6)
    p["out"]["kernel"] = jnp.zeros_like(p["out"]["kernel"]).at[2].set(
        p["out"]["kernel"][2])
    base = module.apply({"params": p}, x)

    def moved(col):
        g = p["gate"]["kernel"].at[:, col].add(1.0)
        return module.apply({"params": dict(p, gate={"kernel": g})}, x)

    np.testing.assert_array_equal(moved(0), base)
    np.testing.assert_array_equal(moved(5), base)
    assert rel(moved(2), base) > 1e-2


# -- the shared code traces as it did ----------------------------------------

def rope_before(x, positions, base=10000.0):
    """``transformer_lm.rope`` before its ``rotary_dim`` and ``yarn``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    while ang.ndim < x.ndim:
        ang = ang[None] if ang.ndim < x.ndim - 1 else ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class AttentionBefore(nn.Module):
    """``lfm2_moe.GroupedQueryAttention`` before its head width, window,
    rotary and gate fields."""

    num_heads: int
    num_kv_heads: int
    rope_theta: float
    norm_eps: float
    dtype: object
    attention_impl: str
    block_q: int
    block_k: int

    @nn.compact
    def __call__(self, x):
        from fluxdistributed_tpu.models.common import rms_norm
        from fluxdistributed_tpu.ops.pallas_attention import flash_attention

        d, t = x.shape[-1], x.shape[1]
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, d // self.num_heads), axis=-1, dtype=self.dtype,
            use_bias=False, name=name)
        norm = lambda name: rms_norm(self.dtype, self.norm_eps, name)  # noqa: E731
        pos = jnp.arange(t)
        q = rope_before(norm("q_norm")(heads(self.num_heads, "q")(x)), pos,
                        base=self.rope_theta)
        k = rope_before(norm("k_norm")(heads(self.num_kv_heads, "k")(x)), pos,
                        base=self.rope_theta)
        v = heads(self.num_kv_heads, "v")(x)
        if self.attention_impl == "pallas":
            out = flash_attention(q, k, v, True, self.block_q, self.block_k)
        else:
            out = dot_product_attention(q, k, v, causal=True)
        return nn.DenseGeneral(d, axis=(-2, -1), dtype=self.dtype,
                               use_bias=False, name="out")(out)


@pytest.mark.parametrize("shape,base", [
    ((4, 4096, 32, 64), 1e6), ((4, 4096, 8, 64), 1e6),   # LFM2's q and k
    ((4, 4096, 20, 64), 1e6), ((4, 4096, 1, 64), 1e6),   # GLM's rotary parts
    ((2, 8192, 8, 128), 1e5)], ids=["lfm2_q", "lfm2_k", "glm_q", "glm_k", "evabyte"])
def test_rope_traces_as_before_at_the_cells_shapes(shape, base):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    pos = jax.ShapeDtypeStruct(shape[1:2], jnp.int32)
    trace = lambda f: str(jax.make_jaxpr(  # noqa: E731
        jax.grad(lambda x, p: jnp.sum(f(x, p, base=base).astype(jnp.float32))))(x, pos))
    assert trace(rope) == trace(rope_before)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_lfm2_attention_traces_as_before_at_its_cells_shapes(impl):
    """The LFM2 cell's attention (32 query and 8 key-value heads of 64,
    rows of 4,096, blocks of 1,024) with the new fields at their
    defaults: forward and gradient, the same program."""
    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")) as f:
        kw = json.load(f)["model"]["kwargs"]
    args = (kw["num_heads"], kw["num_kv_heads"], kw["rope_theta"], kw["norm_eps"],
            jnp.bfloat16, impl, kw["attn_block_q"], kw["attn_block_k"])
    x = jax.ShapeDtypeStruct((4, 4096, kw["dim"]), jnp.bfloat16)

    def trace(module):
        params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p, x: jnp.sum(module.apply(p, x).astype(jnp.float32)),
            argnums=(0, 1)))(params, x))

    assert trace(GroupedQueryAttention(*args)) == trace(AttentionBefore(*args))


# -- the cut, the gauges, what is refused -------------------------------------

def test_the_cut_holds_published_layers_4_to_7_and_the_gauges_count_them():
    kw = dict(cell_config()["model"]["kwargs"])
    cfg = lg.LagunaConfig(**{k: tuple(v) if isinstance(v, list) else v
                             for k, v in kw.items() if k != "dtype"})
    assert [cfg.layer_types[l] for l in cfg.held] == [
        "full_attention"] + ["sliding_attention"] * 3
    assert [cfg.num_heads_per_layer[l] for l in cfg.held] == [48, 72, 72, 72]
    assert cfg.layer_types == lg.PUBLISHED_LAYER_TYPES
    assert cfg.num_heads_per_layer == lg.PUBLISHED_HEADS
    assert (cfg.rotary_dim, cfg.yarn.attention_factor) == (64, 1.4852030263919618)
    lg._publish(cfg, 2, 4096)
    reg = get_registry()
    assert {k: reg.value("fdtpu_layer_kinds", k) for k in ("full", "sliding")} == {
        "full": 1, "sliding": 3}
    full = 2 * 48 * 4096 * 4097 // 2
    sliding = 2 * 3 * 72 * (512 * 513 // 2 + (4096 - 512) * 512)
    assert reg.value("fdtpu_attn_pairs", "full") == full
    assert reg.value("fdtpu_attn_pairs", "sliding") == sliding
    assert round(100 * sliding / (sliding + full), 2) == 51.33


def test_what_is_refused_names_both_counts_and_decode():
    with pytest.raises(ValueError, match=r"num_heads \(48\).*num_kv_heads \(7\)"):
        lg.LagunaConfig(num_kv_heads=7)
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(ValueError, match=r"num_heads \(6\).*num_kv_heads \(4\)"):
        GroupedQueryAttention(6, 4, head_dim=8).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="not all named"):
        lg.LagunaConfig(num_layers=4, layer_offset=46)
    with pytest.raises(NotImplementedError, match="per-head gate"):
        lg.Laguna(lg.LagunaConfig(), decode=True)
