"""LFM2-8B-A1B's modules against the benchmark's plain reference
(``chipbench/configs/lfm2_8b_a1b.py``, which imports nothing of the
program), on the CPU at tiny sizes in float32.

Tolerances: both sides compute in float32 on the CPU in another order of
operations (the program pads once and adds shifted slices, sorts rows
and runs grouped products; the reference shifts tap by tap and runs
every expert over every token), so they differ by float32 rounding over
sums of a few dozen terms: 2e-5 relative to the largest entry is ten
times what was read (about 1e-6).  The Pallas kernels under the
interpreter keep an online softmax, a few roundings more: 1e-4.  The
same modules in bfloat16 read 3e-3 and more (``test_bfloat16_...``), so
a lower precision than float32 fails every comparison here.
"""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, refcommon  # noqa: E402
from fluxdistributed_tpu import models  # noqa: E402
from fluxdistributed_tpu.models.experts import ExpertMLP  # noqa: E402
from fluxdistributed_tpu.models.lfm2_moe import (  # noqa: E402
    NO_DECODE, GroupedQueryAttention, ShortConv, short_conv_core)
from fluxdistributed_tpu.parallel import ep  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")
REF = harness.load_module(os.path.splitext(CONFIG)[0] + ".py")
PREC = refcommon.Precision("f32")
TOL = 2e-5


def tiny_cfg(**over):
    """The configuration's file at the tiny sizes, as the reference
    reads it, and the factory's keywords that say the same."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=32, num_attention_heads=8, num_key_value_heads=2,
        intermediate_size=64, moe_intermediate_size=16, router_experts=32,
        experts_held=[0, 32], num_experts_per_tok=4, num_hidden_layers=3,
        layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
        input={"kind": "tokens", "seq_len": 16, "vocab": 64})
    cfg.update(over)
    cfg["num_hidden_layers"] = len(cfg["layer_types"])
    cfg["model"] = {"factory": "lfm2_moe", "kwargs": dict(
        vocab=cfg["input"]["vocab"], dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        layer_types=cfg["layer_types"], conv_L_cache=cfg["conv_L_cache"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["experts_held"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_dense_layers=cfg["num_dense_layers"],
        bias_update_rate=cfg["bias_update_rate"], dtype="float32")}
    return cfg


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, np.abs(a - b).max() / scale


def trees_close(a, b, tol=TOL):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        close(x, y, tol)


def value_and_grads(f, w):
    """``f``'s result weighted by ``w`` and its gradients in (p, x)."""
    return jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(f(p, x) * w), argnums=(0, 1)))


# -- the gated short convolution ----------------------------------------------

def conv_by_lax(b, c, z, w):
    """A third opinion on the core: XLA's own grouped convolution."""
    u = b * z
    taps = w.shape[-1]
    conv = jax.lax.conv_general_dilated(
        u, w.T[:, None, :], (1,), [(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=u.shape[-1],
        precision=jax.lax.Precision.HIGHEST)
    return c * conv


# a row of 16, and rows shorter than the filter's reach
@pytest.mark.parametrize("t", [16, 2, 1])
@pytest.mark.parametrize("taps", [3, 4])
def test_short_convolution_matches_the_reference_and_xlas_convolution(t, taps):
    cfg = tiny_cfg(conv_L_cache=taps)
    p = REF.make_params(cfg, jax.random.PRNGKey(1))[0]["layer0"]["conv"]
    assert p["filter"].shape == (32, taps)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)
    module = ShortConv(taps, jnp.float32)
    prog = lambda p, x: module.apply({"params": p}, x)  # noqa: E731
    ref = lambda p, x: REF.short_conv(cfg, PREC, p, x)  # noqa: E731
    got, want = value_and_grads(prog, w)(p, x), value_and_grads(ref, w)(p, x)
    close(jax.jit(prog)(p, x), jax.jit(ref)(p, x))
    trees_close(got, want)
    # the core alone, its four gradients too
    args = [jax.random.normal(jax.random.PRNGKey(4 + i), x.shape) for i in range(3)]
    args.append(p["filter"])
    both = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3)))(*args)
    trees_close(both(short_conv_core), both(conv_by_lax))


def test_short_convolution_is_causal_and_reaches_two_positions_back():
    cfg = tiny_cfg()
    p = REF.make_params(cfg, jax.random.PRNGKey(1))[0]["layer0"]["conv"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 32), jnp.float32)
    run = jax.jit(lambda x: ShortConv(3, jnp.float32).apply({"params": p}, x))
    moved = np.abs(np.asarray(run(x.at[0, 7].add(1.0)) - run(x))).max(axis=-1)[0]
    # a change at position 7 moves 7, 8 and 9, nothing before and nothing after
    assert (moved[:7] == 0).all() and (moved[7:10] > 1e-3).all()
    assert (moved[10:] == 0).all()


# -- grouped-query attention --------------------------------------------------

@pytest.mark.parametrize("impl,tol", [("xla", TOL), ("pallas", 1e-4)])
def test_grouped_query_attention_matches_the_reference(impl, tol):
    """8 query heads over 2 key-value heads, four to one as published;
    the per-head norms' weights are not 1, so that they count."""
    cfg = tiny_cfg()
    p = copy.deepcopy(
        REF.make_params(cfg, jax.random.PRNGKey(3))[0]["layer1"]["attn"])
    for i, name in enumerate(("q_norm", "k_norm")):
        p[name]["scale"] = 1.0 + 0.5 * jax.random.normal(
            jax.random.PRNGKey(20 + i), (4,), jnp.float32)
    assert p["k"]["kernel"].shape == (32, 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape, jnp.float32)
    module = GroupedQueryAttention(
        8, 2, rope_theta=cfg["rope_theta"], norm_eps=cfg["norm_eps"],
        dtype=jnp.float32, attention_impl=impl, block_q=8, block_k=8)
    prog = lambda p, x: module.apply({"params": p}, x)  # noqa: E731
    ref = lambda p, x: REF.attention(cfg, PREC, p, x)  # noqa: E731
    close(jax.jit(prog)(p, x), jax.jit(ref)(p, x), tol)
    trees_close(value_and_grads(prog, w)(p, x), value_and_grads(ref, w)(p, x), tol)


def test_the_flash_path_gets_its_keys_and_values_at_their_own_heads(monkeypatch):
    """No repeat of ``k`` and ``v`` in the model: the kernels' index maps
    point a group of query heads at its key-value head."""
    from fluxdistributed_tpu.ops import pallas_attention

    seen = {}

    def spy(q, k, v, causal, block_q, block_k, window):
        seen.update(q=q.shape, k=k.shape, v=v.shape, causal=causal, window=window)
        return q

    monkeypatch.setattr(pallas_attention, "flash_attention", spy)
    module = GroupedQueryAttention(8, 2, dtype=jnp.float32,
                                   attention_impl="pallas")
    jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0),
                                       jnp.zeros((2, 16, 32))))
    assert seen == {"q": (2, 16, 8, 4), "k": (2, 16, 2, 4),
                    "v": (2, 16, 2, 4), "causal": True, "window": None}


# -- the block: an operator by layer kind, dense layers first -----------------

@pytest.mark.parametrize("kinds,lead", [
    (["conv", "full_attention", "conv", "conv", "conv", "full_attention"], 1),
    (["full_attention", "conv"], 0), (["conv", "conv", "full_attention"], 2)])
def test_blocks_follow_layer_types_and_num_dense_layers(kinds, lead):
    from fluxdistributed_tpu.obs.metrics import get_registry

    cfg = tiny_cfg(layer_types=kinds, num_dense_layers=lead, experts_held=[8, 8])
    model = models.lfm2_moe(**json.loads(json.dumps(cfg["model"]["kwargs"])))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)))
    for i, kind in enumerate(kinds):
        layer = shapes["params"][f"layer{i}"]
        assert set(layer) == {"operator_norm", "ffn_norm",
                              "conv" if kind == "conv" else "attn",
                              "mlp" if i < lead else "moe"}
    # tied head and a final norm: no head of its own
    assert set(shapes["params"]) == {"embed", "final_norm"} | {
        f"layer{i}" for i in range(len(kinds))}
    want = jax.eval_shape(lambda k: REF.make_params(cfg, k), jax.random.PRNGKey(0))
    got = (shapes["params"], {"router": shapes["router"]} if lead < len(kinds) else {})
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == jax.tree.map(
        lambda x: (x.shape, x.dtype), want)
    gauge = get_registry().get("fdtpu_layer_kinds")
    assert gauge.value("conv") == kinds.count("conv")
    assert gauge.value("full_attention") == kinds.count("full_attention")


def test_a_layer_kind_the_model_lacks_and_a_list_of_the_wrong_length_raise():
    kw = tiny_cfg()["model"]["kwargs"]
    with pytest.raises(ValueError, match="sliding_window"):
        models.lfm2_moe(**dict(kw, layer_types=["conv", "sliding_window", "conv"]))
    with pytest.raises(ValueError, match="names 2 layers"):
        models.lfm2_moe(**dict(kw, layer_types=["conv", "conv"]))


# -- the expert layer: 8 of 32 held, and all 32 --------------------------------

def skewed_layer(held, tokens=128):
    """A layer's weights and tokens with the router skewed towards
    expert ``held[0] + 1``: its score is the largest for every token."""
    cfg = tiny_cfg(experts_held=list(held))
    params, state = REF.make_params(cfg, jax.random.PRNGKey(7))
    p = copy.deepcopy(params["layer1"])
    mean = jnp.ones((32,)) / jnp.sqrt(32.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 32)) + 4.0 * mean
    p["moe"]["router"] = p["moe"]["router"].at[:, held[0] + 1].set(2.0 * mean)
    return cfg, p, state["router"]["layer1"], x


def program_layer(cfg, p, state, x):
    kw = cfg["model"]["kwargs"]
    routed = ExpertMLP(
        kw["moe_intermediate_size"], kw["n_routed_experts"],
        tuple(cfg["experts_held"]), kw["num_experts_per_tok"],
        cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
        cfg["bias_update_rate"], jnp.float32)
    y, new = routed.apply({"params": p["moe"], "router": state["moe"]}, x,
                          True, mutable=["router"])
    return y, new["router"]


# 1,024 tokens, 4,096 slots: at 8 of 32 held the ladder is 1,536, 2,048, 3,072
# and every slot
@pytest.mark.parametrize("held,tokens", [
    ((0, 8), 128), ((0, 32), 128), ((24, 8), 128), ((0, 8), 1024)])
def test_expert_layer_matches_the_reference_and_drops_nothing(held, tokens):
    cfg, p, state, x = skewed_layer(held, tokens)
    want, ref_state = jax.jit(
        lambda p, x: REF.expert_mlp(cfg, PREC, p, state, x))(p, x)
    y, new = jax.jit(lambda p, x: program_layer(cfg, p, state, x))(p, x)
    close(y, want)
    load = np.asarray(new["load"])
    # every token chose the favoured expert, and each is in the result
    assert load.sum() == 4 * len(x) and load[held[0] + 1] == len(x)
    np.testing.assert_array_equal(load, np.asarray(ref_state["moe"]["load"]))
    model = models.lfm2_moe(**cfg["model"]["kwargs"])
    counted = model.step_metrics({"router": {"layer1": new}})
    assert float(counted["moe_dropped"]) == 0.0
    slots_here = load[held[0]:held[0] + held[1]].sum()
    assert float(counted["moe_slots"][0]) == slots_here
    taken = next(rows for rows in ep.compact_rows(4 * tokens, held[1], 32)
                 if slots_here <= rows)
    assert [int(n) for n in counted["moe_rows"]] == [slots_here, taken]
    fits = taken < 4 * tokens
    assert [int(n) for n in counted["moe_compact"]] == [int(fits), int(not fits)]
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)
    trees_close(
        value_and_grads(lambda p, x: program_layer(cfg, p, state, x)[0], w)(p, x),
        value_and_grads(lambda p, x: REF.expert_mlp(cfg, PREC, p, state, x)[0],
                        w)(p, x))


def test_the_four_shares_add_up_to_the_uncut_expert_block():
    """Experts 0-7, 8-15, 16-23, 24-31 of one layer, each share's routed
    part by the program: their sum is the uncut reference's block (there
    is no shared expert to count once)."""
    cfg, p, state, x = skewed_layer((0, 32))
    want, _ = jax.jit(lambda: REF.expert_mlp(cfg, PREC, p, state, x))()
    chosen, weights, load = ep.sigmoid_route(
        x, p["moe"]["router"], state["moe"]["bias"], top_k=4,
        scale=cfg["routed_scaling_factor"], normalize=cfg["norm_topk_prob"])
    total = 0.0
    for first in (0, 8, 16, 24):
        part = {k: p["moe"][k][first:first + 8]
                for k in ("w_gate", "w_up", "w_down")}
        share = dict(cfg, experts_held=[first, 8])
        mine = ep.held_experts_apply(x, chosen, weights, *part.values(), 32,
                                     first=first)
        close(mine, REF.routed_part(share, PREC, part, x, chosen, weights))
        total = total + mine
    close(total, want)
    assert float(load.sum()) == 4 * len(x)


# -- the whole model: tied head, the bias step ---------------------------------

def whole_model(**over):
    cfg = tiny_cfg(router_experts=8, experts_held=[2, 4], num_experts_per_tok=2,
                   **over)
    model = models.lfm2_moe(**cfg["model"]["kwargs"])
    params, state = REF.make_params(cfg, jax.random.PRNGKey(11))
    return cfg, model, params, state


def test_loss_gradients_and_bias_step_match_the_reference_over_three_steps():
    """Three training steps' losses, gradients and router states, the
    parameters held still so that only the routers' bias carries over.
    A bias step of 0.05 (not 0.001) makes the bias move the choice."""
    cfg, model, params, state = whole_model(bias_update_rate=0.05)
    loss_fn = models.lm_loss_fn(model)
    rng = np.random.default_rng(12)
    ref_state, biases = state, []

    @jax.jit
    def prog_step(state, tokens):
        return jax.value_and_grad(
            lambda p: loss_fn(p, state, {"tokens": tokens}, True),
            has_aux=True)(params)

    @jax.jit
    def ref_row(state, row):
        return jax.value_and_grad(
            lambda p: REF.row_loss_sum(cfg, PREC, p, state, row[None]),
            has_aux=True)(params)

    for step in range(3):
        tokens = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)
        (loss, (new, _)), grads = prog_step(state, tokens)
        total, blocks, g_sum = 0.0, [], None
        for row in tokens:
            (l, s), g = ref_row(ref_state, row)
            total, blocks = total + l, blocks + [s]
            g_sum = g if g_sum is None else jax.tree.map(jnp.add, g_sum, g)
        ref_new = REF.merge_state(cfg, ref_state, blocks, [1] * len(tokens))
        close(loss, total / len(tokens))
        trees_close(grads, jax.tree.map(lambda g: g / len(tokens), g_sum), 1e-4)
        trees_close(new, ref_new, 1e-6)
        state, ref_state = new, ref_new
        biases.append(np.asarray(new["router"]["layer2"]["moe"]["bias"]))
    # the bias is there after one step and goes on moving
    assert np.abs(biases[0]).max() == np.float32(0.05)
    assert not np.array_equal(biases[0], biases[1])
    assert not np.array_equal(biases[1], biases[2])


def test_the_embeddings_gradient_holds_the_lookup_and_the_head():
    cfg, model, params, state = whole_model()
    tokens = jnp.asarray(np.random.default_rng(13).integers(0, 32, (2, 16)),
                         jnp.int32)
    loss_fn = models.lm_loss_fn(model)

    def loss(table):
        p = dict(params, embed={"embedding": table})
        return loss_fn(p, state, {"tokens": tokens}, True)[0]

    g = np.asarray(jax.jit(jax.grad(loss))(params["embed"]["embedding"]))
    # ids 32-63 are never looked up: their rows move by the head alone;
    # rows that are looked up hold more than the head's part
    looked_up = np.unique(np.asarray(tokens))
    assert (np.abs(g[32:]).max(axis=-1) > 0).all()
    head_only = np.median(np.linalg.norm(g[32:], axis=-1))
    assert np.median(np.linalg.norm(g[looked_up], axis=-1)) > 2 * head_only
    want = jax.jit(jax.grad(lambda p: REF.row_loss_sum(
        cfg, PREC, p, state, tokens)[0] / len(tokens)))(params)
    close(g, want["embed"]["embedding"], 1e-4)
    # an untied model has a head of its own
    untied = models.lfm2_moe(**dict(cfg["model"]["kwargs"], tie_embedding=False))
    shapes = jax.eval_shape(lambda: untied.init(jax.random.PRNGKey(0), tokens))
    assert shapes["params"]["head"]["kernel"].shape == (32, 64)


def test_bfloat16_in_place_of_float32_fails_the_tolerances():
    """The comparisons above are tight enough to tell a lower precision:
    the same model computing in bfloat16 is 100 times the tolerance off."""
    cfg, model, params, state = whole_model()
    low = models.lfm2_moe(**dict(cfg["model"]["kwargs"], dtype="bfloat16"))
    tokens = jnp.asarray(np.random.default_rng(14).integers(0, 64, (2, 16)),
                         jnp.int32)
    variables = {"params": params, **state}
    want = jax.jit(lambda: model.apply(variables, tokens, False))()
    got = jax.jit(lambda: low.apply(variables, tokens, False))()
    gap = np.abs(np.asarray(got - want)).max() / np.abs(np.asarray(want)).max()
    assert gap > 100 * TOL, gap
    with pytest.raises(AssertionError):
        close(got, want, 1e-4)


def test_eval_leaves_the_routers_alone():
    cfg, model, params, state = whole_model()
    tokens = jnp.asarray(np.random.default_rng(15).integers(0, 64, (2, 16)),
                         jnp.int32)
    loss, (new, logits) = jax.jit(lambda: models.lm_loss_fn(model)(
        params, state, {"tokens": tokens}, False))()
    trees_close(new, state, 0.0)
    assert logits.shape == (2, 16, 64) and logits.dtype == jnp.float32
    close(loss, models.next_token_loss(logits, tokens))


def test_remat_changes_no_name_and_no_number():
    cfg, model, params, state = whole_model()
    again = models.lfm2_moe(**dict(cfg["model"]["kwargs"], remat=True))
    tokens = jnp.asarray(np.random.default_rng(16).integers(0, 64, (2, 16)),
                         jnp.int32)
    step = lambda m: jax.jit(jax.value_and_grad(lambda p: models.lm_loss_fn(m)(  # noqa: E731
        p, state, {"tokens": tokens}, True)[0]))(params)
    trees_close(step(again), step(model), 1e-6)


# -- the factory, the count, and what is not built ------------------------------

def test_factory_takes_json_alone_and_counts_the_cuts_parameters():
    with open(CONFIG) as f:
        cfg = json.load(f)
    kw = json.loads(json.dumps(cfg["model"]["kwargs"]))
    model = models.lfm2_moe(**kw)
    assert model.cfg.experts_held == (0, 8) and model.cfg.dtype == jnp.bfloat16
    assert model.cfg.layer_types == (
        "conv", "full_attention", "conv", "conv", "conv", "full_attention")
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))  # noqa: E731
    p = shapes["params"]
    assert count(p["layer0"]) == 60_827_648          # conv, dense SwiGLU
    assert count(p["layer1"]) == 98_635_904          # attention, 8 experts
    assert count(p["layer2"]) == 104_933_376         # conv, 8 experts
    assert count(p["layer1"]["attn"]) == 10_485_888
    assert count(p["layer2"]["conv"]) == 16_783_360
    assert count(p) == cfg["parameters"] == 606_456_064
    want = jax.eval_shape(lambda k: REF.make_params(cfg, k), jax.random.PRNGKey(0))
    got = (p, {"router": shapes["router"]})
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == jax.tree.map(
        lambda x: (x.shape, x.dtype), want)
    # the file's cut is the published list's entries 1-6, and the model's
    # default list is the published one
    assert cfg["layer_types"] == cfg["published"]["layer_types"][1:7]
    assert list(models.Lfm2Config(vocab=8).layer_types) == cfg["published"]["layer_types"]
    # whole: 8.34B in all, with one 134M embedding
    uncut = models.lfm2_moe(vocab=65536)
    whole = jax.eval_shape(lambda: uncut.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert round(count(whole["params"]) / 1e9, 2) == 8.34


def test_forward_macs_are_the_issues_by_hand():
    with open(CONFIG) as f:
        cfg = json.load(f)
    t, d = 4096, 2048
    conv = 4 * d * d + 3 * d
    attn_proj = 2 * d * d + 2 * d * 512
    pairs = t * (t + 1) // 2
    expert = 3 * d * 1792
    want = (t * (4 * conv + 2 * attn_proj) + 2 * pairs * 32 * 2 * 64
            + t * 3 * d * 7168 + 5 * t * (d * 32 + 4 * 8 / 32 * expert)
            + t * d * 16384)
    assert REF.forward_macs(cfg) == int(want)
    assert 237.8 < REF.forward_macs(cfg) / t / 1e6 < 237.9  # the issue: 237.8M
    assert round(REF.forward_macs(cfg) / 1e12, 3) == 0.974


@pytest.mark.parametrize("how", ["decode", "engine"])
def test_serving_fails_with_the_name_of_what_is_missing(how):
    model = whole_model()[1]
    with pytest.raises(NotImplementedError,
                       match="convolution's last two inputs") as e:
        if how == "decode":  # and so generate(), which wants such a clone
            model.clone(decode=True)
        else:
            from fluxdistributed_tpu.serve.engine import LMEngine

            LMEngine(model, {})
    assert str(e.value) == NO_DECODE
