"""GLM-4.7-Flash's modules against the benchmark's plain reference
(``chipbench/configs/glm47_flash.py``, which imports nothing of the
program), on the CPU at tiny sizes in float32.

Tolerances: both sides compute in float32 on the CPU, in another order
of operations (the program sorts rows and runs grouped products, the
reference runs every expert over every token), so they differ by
float32 rounding over sums of a few dozen terms: 2e-5 relative to the
largest entry is ten times what was read (about 1e-6).  The Pallas
kernels under the interpreter keep an online softmax, a few roundings
more: 1e-4.
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
from fluxdistributed_tpu.models.experts import ExpertMLP, SwiGLU  # noqa: E402
from fluxdistributed_tpu.models.glm4_moe_lite import (  # noqa: E402
    LatentAttention, NO_DECODE)
from fluxdistributed_tpu.parallel import ep  # noqa: E402

REF = harness.load_module(
    os.path.join(ROOT, "chipbench", "configs", "glm47_flash.py"))
PREC = refcommon.Precision("f32")
TOL = 2e-5


def tiny_cfg(**over):
    """The configuration's file at the tiny sizes, as the reference
    reads it, and the factory's keywords that say the same."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "glm47_flash.json")) as f:
        cfg = json.load(f)
    cfg.update(
        hidden_size=32, num_attention_heads=2, q_lora_rank=16, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=64, moe_intermediate_size=16, router_experts=64,
        experts_held=[0, 64], num_experts_per_tok=4, num_hidden_layers=2,
        num_nextn_predict_layers=1, input={"kind": "tokens", "seq_len": 16,
                                           "vocab": 64})
    cfg.update(over)
    cfg["model"] = {"factory": "glm4_moe_lite", "kwargs": dict(
        vocab=cfg["input"]["vocab"], dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["experts_held"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        bias_update_rate=cfg["bias_update_rate"],
        mtp_weight=cfg["mtp_weight"], dtype="float32")}
    return cfg


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    assert np.abs(a - b).max() <= tol * scale, np.abs(a - b).max() / scale


def trees_close(a, b, tol=TOL):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        close(x, y, tol)


# -- latent attention ---------------------------------------------------------

@pytest.mark.parametrize("impl,tol", [("xla", TOL), ("pallas", 1e-4)])
def test_latent_attention_matches_the_reference(impl, tol):
    cfg = tiny_cfg()
    params, _ = REF.make_params(cfg, jax.random.PRNGKey(3))
    p = params["layer0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32), jnp.float32)
    kw = cfg["model"]["kwargs"]
    module = LatentAttention(
        kw["num_heads"], kw["q_lora_rank"], kw["kv_lora_rank"],
        kw["qk_nope_head_dim"], kw["qk_rope_head_dim"], kw["v_head_dim"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.float32, attention_impl=impl, block_q=8, block_k=8)

    def prog(p, x):
        return module.apply({"params": p}, x)

    def ref(p, x):
        return REF.attention(cfg, PREC, p, x)

    w = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 32), jnp.float32)
    both = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p, x: jnp.sum(f(p, x) * w), argnums=(0, 1)))
    close(jax.jit(prog)(p, x), jax.jit(ref)(p, x), tol)
    trees_close(both(prog)(p, x), both(ref)(p, x), tol)


def test_the_flash_path_wants_one_head_size():
    module = LatentAttention(2, 16, 8, 8, 8, 8, dtype=jnp.float32,
                             attention_impl="pallas")
    x = jnp.zeros((1, 16, 32), jnp.float32)
    with pytest.raises(ValueError, match="v_head_dim"):
        module.init(jax.random.PRNGKey(0), x)


# -- the expert layer ---------------------------------------------------------

def skewed_layer(held, tokens=128, skew=True, bias=0.0):
    """A layer's weights and tokens with the router skewed towards
    expert ``held[0] + 1``: its score is the largest for every token
    (``skew=False``: the seeded router as it is, an even load), and a
    selection bias of ``bias`` on the held experts."""
    cfg = tiny_cfg(experts_held=list(held), num_nextn_predict_layers=0)
    params, state = REF.make_params(cfg, jax.random.PRNGKey(7))
    p = copy.deepcopy(params["layer1"])
    x = jax.random.normal(jax.random.PRNGKey(8), (tokens, 32), jnp.float32)
    if skew:
        # a column along the tokens' mean direction, and tokens with a mean
        mean = jnp.ones((32,)) / jnp.sqrt(32.0)
        x = x + 4.0 * mean
        p["moe"]["router"] = p["moe"]["router"].at[:, held[0] + 1].set(
            2.0 * mean)
    state = copy.deepcopy(state["router"]["layer1"])
    state["moe"]["bias"] = state["moe"]["bias"].at[
        held[0]:held[0] + held[1]].add(bias)
    return cfg, p, state, x


def path_counted(cfg, router_state):
    """[compact, full] and [live, taken] rows as the model's step
    metrics count one layer."""
    model = models.glm4_moe_lite(**cfg["model"]["kwargs"])
    counted = model.step_metrics({"router": {"layer1": router_state}})
    return ([int(n) for n in counted["moe_compact"]],
            [int(n) for n in counted["moe_rows"]])


def program_layer(cfg, p, state, x):
    kw = cfg["model"]["kwargs"]
    routed = ExpertMLP(
        kw["moe_intermediate_size"], kw["n_routed_experts"],
        tuple(cfg["experts_held"]), kw["num_experts_per_tok"],
        cfg["routed_scaling_factor"], cfg["norm_topk_prob"],
        cfg["bias_update_rate"], jnp.float32)
    y, new = routed.apply({"params": p["moe"], "router": state["moe"]}, x,
                          True, mutable=["router"])
    shared = SwiGLU(kw["moe_intermediate_size"], jnp.float32).apply(
        {"params": p["shared"]}, x)
    return y, shared, new["router"]


# 128 tokens: the only rung is all 512 slots, one path and no branch.
# 1,024 tokens: 4,096 slots, a ladder of 1,024, 1,536 and every slot; the
# even router holds some 512 slots and takes the first, the skewed one
# 1,300-1,536 and the second, a bias on the held experts the whole buffer.
# 8,192 tokens: 32,768 slots and all four rungs (4,608, 6,144, 8,192,
# 12,288); the even router holds some 4,800, and a selection bias on the
# held experts lands the step on each rung in turn
@pytest.mark.parametrize("held,tokens,skew,bias,taken", [
    ((0, 8), 128, True, 0, 512), ((0, 64), 128, True, 0, 512),
    ((24, 8), 128, True, 0, 512), ((0, 8), 1024, True, 0, 1536),
    ((0, 8), 1024, False, 0, 1024), ((24, 8), 1024, False, 0, 1024),
    ((24, 8), 1024, False, 0.15, 4096),
    ((0, 8), 8192, False, -0.02, 4608), ((24, 8), 8192, False, 0, 6144),
    ((0, 8), 8192, False, 0.05, 8192), ((24, 8), 8192, False, 0.1, 12288),
    ((0, 8), 8192, False, 0.15, 32768)])
def test_expert_layer_matches_the_reference_and_drops_nothing(
        held, tokens, skew, bias, taken):
    cfg, p, state, x = skewed_layer(held, tokens, skew, bias)
    want, ref_state = jax.jit(
        lambda p, x: REF.expert_mlp(cfg, PREC, p, state, x))(p, x)
    y, shared, new = jax.jit(
        lambda p, x: program_layer(cfg, p, state, x))(p, x)
    close(y + shared, want)
    load = np.asarray(new["load"])
    assert load.sum() == 4 * len(x)
    if skew:
        # every token chose the favoured expert: over half of them on one
        # expert, and each is in the result (the reference has no capacity)
        assert load[held[0] + 1] == len(x)
    np.testing.assert_array_equal(load, np.asarray(ref_state["moe"]["load"]))
    # the rung the layer's own ladder gives this load, from the step's metrics
    live = int(load[held[0]:held[0] + held[1]].sum())
    ladder = ep.compact_rows(4 * tokens, held[1], 64)
    assert taken == next(rows for rows in ladder if live <= rows)
    below = int(taken < 4 * tokens)
    assert path_counted(cfg, new) == ([below, 1 - below], [live, taken])
    # gradients of the routed part, the router's among them
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)

    def prog(p, x):
        y, shared, _ = program_layer(cfg, p, state, x)
        return jnp.sum((y + shared) * w)

    def ref(p, x):
        return jnp.sum(REF.expert_mlp(cfg, PREC, p, state, x)[0] * w)

    trees_close(jax.jit(jax.grad(prog, argnums=(0, 1)))(p, x),
                jax.jit(jax.grad(ref, argnums=(0, 1)))(p, x))


def routed_grads(args, first):
    """The routed part and its five gradients, through a fresh trace."""
    def out(x, weights, w_gate, w_up, w_down, chosen):
        return ep.held_experts_apply(x, chosen, weights, w_gate, w_up, w_down,
                                     64, first=first)

    w = jax.random.normal(jax.random.PRNGKey(10), args[0].shape, jnp.float32)
    text = str(jax.make_jaxpr(out)(*args))
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(out(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args), text


# 8,192 tokens, 32,768 slots, 8 of 64 held: the ladder by hand, and held
# slots that land on each rung in turn and on the whole buffer
LADDER = (4608, 6144, 8192, 12288, 32768)


@pytest.mark.parametrize("rung,live", enumerate((4000, 6144, 7000, 8193, 20000)))
@pytest.mark.parametrize("first", [0, 24])
def test_every_rung_and_the_whole_buffer_agree(first, rung, live, monkeypatch):
    """The same step over the rung its held slots choose and over all
    32,768 rows: output and the five gradients to float32 rounding, and
    both the reference's."""
    cfg, p, state, x = skewed_layer((first, 8), 8192, skew=False)
    _, weights, _ = ep.sigmoid_route(
        x, p["moe"]["router"], state["moe"]["bias"], top_k=4,
        scale=cfg["routed_scaling_factor"])
    # the first ``live`` slots to the held experts, the others to absent ones
    rng = np.random.default_rng(17 + rung)
    chosen = (first + 8 + rng.integers(0, 56, 4 * len(x))) % 64
    chosen[:live] = first + rng.integers(0, 8, live)
    chosen = jnp.asarray(chosen.reshape(len(x), 4), jnp.int32)
    assert ep.compact_rows(4 * len(x), 8, 64) == LADDER
    assert LADDER[rung] == next(rows for rows in LADDER if live <= rows)
    part = {k: p["moe"][k] for k in ("w_gate", "w_up", "w_down")}
    args = (x, weights, *part.values(), chosen)
    bounded, text = routed_grads(args, first)
    assert " cond[" in text
    # no rung below every slot: one path, no branch
    monkeypatch.setattr(ep, "COMPACT_OVER_EXPECTED", ())
    whole, text = routed_grads(args, first)
    assert " cond[" not in text
    trees_close(bounded, whole)

    def ref(x, weights, w_gate, w_up, w_down):
        part = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        return REF.routed_part(cfg, PREC, part, x, chosen, weights)

    w = jax.random.normal(jax.random.PRNGKey(10), x.shape, jnp.float32)
    want = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args[:5])
    trees_close(bounded, want)


@pytest.mark.parametrize("slots,held,experts,ladder", [
    # the GLM cell: 1.125, 1.5, 2 and 3 times the 8,192 expected, every slot
    (65536, 8, 64, (9216, 12288, 16384, 24576, 65536)),
    # the LFM2 cell: the same of 16,384
    (65536, 8, 32, (18432, 24576, 32768, 49152, 65536)),
    (65536, 64, 64, (65536,)),   # every expert held
    (65536, 60, 64, (65536,)),   # most of them: 1.125 times is over the slots
    (65536, 40, 64, (46080, 61440, 65536)),  # the rungs under the slots stay
    (512, 4, 8, (512,)),         # the tiny sizes
    # up to a tile of 512, and a rung met twice counts once
    (4096, 8, 64, (1024, 1536, 4096)), (3840, 8, 64, (1024, 1536, 3840))])
def test_the_ladder_by_hand_and_no_branch_where_it_is_one_rung(
        slots, held, experts, ladder):
    assert ep.compact_rows(slots, held, experts) == ladder
    assert len(ladder) <= 5 and all(r % 512 == 0 for r in ladder[:-1])
    # the same of an array of loads, as the step's metrics call it: none left
    # out, none over the slots
    rungs = [int(r) for r in ep.compact_rows(jnp.int32(slots), held, experts)]
    assert len(rungs) == 5 and sorted(set(rungs)) == list(ladder)
    n, k = slots // 64, 4  # the layer at a sixteenth of the slots, 4 a token
    small = ep.compact_rows(n * k, held, experts)
    text = str(jax.make_jaxpr(
        lambda x, c, w, a, b: ep.held_experts_apply(x, c, w, a, a, b, experts))(
            jnp.zeros((n, 8)), jnp.zeros((n, k), jnp.int32), jnp.zeros((n, k)),
            jnp.zeros((held, 8, 4)), jnp.zeros((held, 4, 8))))
    assert (" cond[" in text) == (len(small) > 1)


def test_the_ladder_keeps_what_the_chip_runs_fixed():
    """No step gets a longer buffer than two lengths gave it: the rung
    at twice the even share stays, one lies under 1.25 and one between 2
    and every slot; four at most, each a compiled copy of the layer."""
    over = ep.COMPACT_OVER_EXPECTED
    assert list(over) == sorted(over) and len(over) <= 4
    assert 2 in over and over[0] <= 1.25 and any(2 < o for o in over)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that eight chips with eight
    experts each compute, plus the shared expert counted once, are the
    uncut reference's layer."""
    cfg, p, state, x = skewed_layer((0, 64))
    want, _ = REF.expert_mlp(cfg, PREC, p, state, x)
    chosen, weights, load = ep.sigmoid_route(
        x, p["moe"]["router"], state["moe"]["bias"], top_k=4,
        scale=cfg["routed_scaling_factor"])
    total = SwiGLU(16, jnp.float32).apply({"params": p["shared"]}, x)
    for first in range(0, 64, 8):
        part = {k: p["moe"][k][first:first + 8]
                for k in ("w_gate", "w_up", "w_down")}
        total = total + ep.held_experts_apply(
            x, chosen, weights, part["w_gate"], part["w_up"], part["w_down"],
            64, first=first)
        # the same share through the reference
        share = dict(cfg, experts_held=[first, 8])
        close(ep.held_experts_apply(x, chosen, weights, *part.values(),
                                    64, first=first),
              REF.routed_part(share, PREC, part, x, chosen, weights))
    close(total, want)
    assert float(load.sum()) == 4 * len(x)


def test_a_selection_bias_moves_the_choice_and_not_the_weights():
    cfg, p, state, x = skewed_layer((0, 64))
    router = p["moe"]["router"]
    none = ep.sigmoid_route(x, router, jnp.zeros((64,)), top_k=4)
    bias = jnp.zeros((64,)).at[5].set(10.0)
    chosen, weights, load = ep.sigmoid_route(x, router, bias, top_k=4)
    assert float(load[5]) == len(x) > float(none[2][5])
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    close(weights, picked / picked.sum(-1, keepdims=True))


# -- the loss: the multi-token term, the bias step ----------------------------

def test_mtp_term_and_bias_step_match_the_reference_over_three_steps():
    """Three training steps' losses, gradients and router states, the
    parameters held still so that only the routers' bias carries over.
    A bias step of 0.05 (not 0.001) makes the bias move the choice."""
    cfg = tiny_cfg(router_experts=8, experts_held=[2, 4],
                   num_experts_per_tok=2, bias_update_rate=0.05)
    model = getattr(models, cfg["model"]["factory"])(**cfg["model"]["kwargs"])
    loss_fn = models.lm_loss_fn(model)
    params, state = REF.make_params(cfg, jax.random.PRNGKey(11))
    rng = np.random.default_rng(12)
    ref_state = state

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
        # the reference: a block a row, the states merged over the blocks
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
    bias = np.asarray(state["router"]["mtp0"]["block"]["moe"]["bias"])
    assert np.abs(bias).max() > 0.05  # it moved more than one step's worth
    # the term is there: without the module the loss is another
    plain = models.lm_loss_fn(model.clone(cfg=model.cfg.__class__(**{
        **model.cfg.__dict__, "num_nextn_predict_layers": 0})))
    main = {k: v for k, v in params.items() if k != "mtp0"}
    main_state = {"router": {k: v for k, v in state["router"].items()
                             if k != "mtp0"}}
    without = jax.jit(lambda: plain(
        main, main_state, {"tokens": tokens}, True)[0])()
    with_term = prog_step(state, tokens)[0][0]
    assert float(with_term - without) > 0.3 * 3.0  # 0.3 x about ln 64


def test_eval_leaves_the_routers_alone_and_adds_no_multi_token_term():
    cfg = tiny_cfg(router_experts=8, experts_held=[0, 8], num_experts_per_tok=2)
    model = models.glm4_moe_lite(**cfg["model"]["kwargs"])
    loss_fn = models.lm_loss_fn(model)
    params, state = REF.make_params(cfg, jax.random.PRNGKey(13))
    tokens = jnp.asarray(np.random.default_rng(14).integers(0, 64, (2, 16)),
                         jnp.int32)
    loss, (new, logits) = jax.jit(
        lambda: loss_fn(params, state, {"tokens": tokens}, False))()
    trees_close(new, state, 0.0)
    assert logits.shape == (2, 16, 64)
    close(loss, models.next_token_loss(logits, tokens))


@pytest.mark.parametrize("sizes,rows,paths,taken", [
    # 128 and 120 slots: the only rung is every slot, no branch, counted whole
    (dict(router_experts=8, experts_held=[2, 4], num_experts_per_tok=2), 4,
     [0, 2], 128 + 120),
    # 4,096 and 3,840 slots of which an eighth are held: 1,024 rows hold them
    (dict(router_experts=64, experts_held=[8, 8], num_experts_per_tok=4), 64,
     [2, 0], 1024 + 1024)])
def test_step_metrics_carry_the_load_through_the_step_to_the_counters(
        sizes, rows, paths, taken):
    """The step's metrics hold what the model reports of its routers,
    and the trainer's watcher callback feeds the registry from them; a
    model without a router reports and registers nothing."""
    import fluxdistributed_tpu as fd
    from fluxdistributed_tpu.obs.metrics import Registry
    from fluxdistributed_tpu.parallel.dp import TrainState, make_train_step
    from fluxdistributed_tpu.train.trainer import _RouterCounters

    cfg = tiny_cfg(**sizes)
    model = models.glm4_moe_lite(**cfg["model"]["kwargs"])
    params, state = REF.make_params(cfg, jax.random.PRNGKey(15))
    opt = fd.optim.adamw(lr=1e-3)
    mesh = fd.data_mesh(devs=jax.devices()[:1])
    step = make_train_step(models.lm_loss_fn(model), opt, mesh, donate=False)
    tokens = jnp.asarray(np.random.default_rng(16).integers(0, 64, (rows, 16)),
                         jnp.int32)
    new, metrics = step(TrainState.create(params, opt, model_state=state),
                        {"tokens": tokens})
    load = np.asarray(metrics["moe_load"])
    k, (first, count) = sizes["num_experts_per_tok"], sizes["experts_held"]
    # layer1 and the multi-token module's block
    assert load.shape == (2, sizes["router_experts"])
    assert load[0].sum() == rows * 16 * k and load[1].sum() == rows * 15 * k
    held, absent = np.asarray(metrics["moe_slots"])
    assert held == load[:, first:first + count].sum()
    assert held + absent == load.sum()
    assert float(metrics["moe_dropped"]) == 0.0
    assert [int(n) for n in metrics["moe_compact"]] == paths
    assert [int(n) for n in metrics["moe_rows"]] == [held, taken]
    np.testing.assert_array_equal(
        load[0], np.asarray(new.model_state["router"]["layer1"]["moe"]["load"]))

    reg = Registry()
    feed = _RouterCounters(reg)
    feed({"loss": metrics["loss"]})
    assert reg.get("fdtpu_moe_slots_total") is None  # no router, nothing
    feed(metrics)
    assert reg.value("fdtpu_moe_slots_total", "held") == held
    assert reg.value("fdtpu_moe_slots_total", "absent") == absent
    assert reg.value("fdtpu_moe_dropped_total") == 0
    balance = reg.get("fdtpu_moe_load_max_over_mean")
    assert balance.cell_count("0") == balance.cell_count("1") == 1
    assert balance.cell_sum("0") == pytest.approx(load[0].max() / load[0].mean())
    feed(metrics)  # a second step: its layers are counted on top
    assert [reg.value("fdtpu_moe_compact_total", path) / 2
            for path in ("compact", "full")] == paths
    assert [reg.value("fdtpu_moe_buffer_rows_total", kind) / 2
            for kind in ("live", "taken")] == [held, taken]

    dense = models.lm_tiny(vocab=64)
    assert not hasattr(models.lm_loss_fn(dense), "step_metrics")


# -- the factory, and what is not built ---------------------------------------

def test_factory_takes_json_alone_and_counts_the_published_parameters():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "glm47_flash.json")) as f:
        cfg = json.load(f)
    kw = json.loads(json.dumps(cfg["model"]["kwargs"]))
    model = models.glm4_moe_lite(**kw)
    assert model.cfg.experts_held == (0, 8) and model.cfg.dtype == jnp.bfloat16
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 128), jnp.int32)))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["params"]))
    assert count == cfg["parameters"] == 591_294_720
    want = jax.eval_shape(lambda k: REF.make_params(cfg, k), jax.random.PRNGKey(0))
    got = (shapes["params"], {"router": shapes["router"]})
    assert jax.tree.map(lambda x: (x.shape, x.dtype), got) == jax.tree.map(
        lambda x: (x.shape, x.dtype), want)
    # with the multi-token module: what the issue counts, 706,518,528
    with_mtp = models.glm4_moe_lite(**dict(kw, num_nextn_predict_layers=1))
    shapes = jax.eval_shape(
        lambda: with_mtp.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 128), jnp.int32)))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes["params"])) == 706_518_528


def test_forward_macs_are_the_issues_by_hand():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "glm47_flash.json")) as f:
        cfg = json.load(f)
    t = 4096
    proj = 2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 + 5120 * 2048
    attn = t * proj + (t * (t + 1) // 2) * 20 * 512
    expert = 3 * 2048 * 1536
    sparse = 2048 * 64 + expert + 4 * 8 / 64 * expert
    want = 5 * attn + t * 3 * 2048 * 10240 + 4 * t * sparse + t * 2048 * 19360
    assert REF.forward_macs(cfg) == int(want)
    assert round(REF.forward_macs(cfg) / t / 1e6, 1) == 373.4


@pytest.mark.parametrize("how", ["decode", "engine"])
def test_serving_fails_with_the_name_of_what_is_missing(how):
    cfg = tiny_cfg(router_experts=8, experts_held=[0, 8], num_experts_per_tok=2)
    model = models.glm4_moe_lite(**cfg["model"]["kwargs"])
    with pytest.raises(NotImplementedError, match="latent cache row") as e:
        if how == "decode":  # and so generate(), which wants such a clone
            model.clone(decode=True)
        else:
            from fluxdistributed_tpu.serve.engine import LMEngine

            LMEngine(model, {})
    assert str(e.value) == NO_DECODE
