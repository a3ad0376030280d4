"""EvaByte on the CPU at tiny sizes in float32: the EVA layer against the
plain reference's (``chipbench/configs/evabyte.py``), forward and
gradients, on both paths; what a query sees; causality; the ``lse``
merge; the head shares adding up to the uncut layer; the unit-offset
norm, the float32 stream and the eight-headed loss.

Tolerances: both sides compute in float32 with products at ``highest``,
so they differ by the order of their sums alone: 2e-5 relative is ten
times what the runs read (under 2e-6) and a hundredth of what bfloat16
in any one place gives (3e-3 and up, held below)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fluxdistributed_tpu as fd
from fluxdistributed_tpu.models.common import rms_norm
from fluxdistributed_tpu.models.evabyte import (NO_DECODE, EvaAttention,
                                                EvaByteConfig)
from fluxdistributed_tpu.obs import get_registry
from fluxdistributed_tpu.ops.attention import dot_product_attention
from fluxdistributed_tpu.ops.eva_attention import (chunk_summaries,
                                                   eva_attention, eva_pairs,
                                                   merge_by_lse)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, refcommon  # noqa: E402

REF = harness.load_module(os.path.join(ROOT, "chipbench", "configs", "evabyte.py"))
PREC = refcommon.Precision("f32")
WINDOW, CHUNK = 32, 4
TOL = 2e-5


def full_config():
    with open(os.path.join(ROOT, "chipbench", "configs", "evabyte.json")) as f:
        return json.load(f)


def tiny(seq_len=128, heads=4, held=(0, 4), layers=2, **kw):
    """(reference's cfg, the program's factory keywords) at a tiny size."""
    cfg = dict(full_config(), hidden_size=32, layer_heads=heads,
               heads_held=list(held), intermediate_size=64, window_size=WINDOW,
               chunk_size=CHUNK, num_hidden_layers=layers,
               input={"kind": "tokens", "seq_len": seq_len, "vocab": 32})
    model = dict(vocab=32, dim=32, num_layers=layers, num_heads=heads,
                 heads_held=list(held), intermediate_size=64,
                 window_size=WINDOW, chunk_size=CHUNK, dtype="float32",
                 attn_block_q=16, attn_block_k=16, **kw)
    return cfg, model


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def qkv(t, h=2, d=8, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(x, (b, t, h, d)) for x in ks[:3])
    mu, phi = (0.5 * jax.random.normal(x, (h, d)) for x in ks[3:])
    return q, k, v, mu, phi


def eva(q, k, v, mu, phi, impl="xla"):
    return eva_attention(q, k, v, mu, phi, window=WINDOW, chunk=CHUNK,
                         impl=impl, block_q=16, block_k=16)


# -- the layer against the reference's ----------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("windows", [1, 2, 4])
def test_the_layer_matches_the_references_forward_and_gradients(impl, windows):
    t = windows * WINDOW
    cfg, kw = tiny(seq_len=t)
    layer = EvaAttention(EvaByteConfig(**fd.models.common.json_kwargs(
        dict(kw, attention_impl=impl), "heads_held")))
    params, _ = REF.make_params(cfg, jax.random.PRNGKey(7))
    p = params["layer0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, 32))
    probe = jax.random.normal(jax.random.PRNGKey(2), (2, t, 32))
    with jax.default_matmul_precision("highest"):
        got, g = jax.value_and_grad(
            lambda p, x: (layer.apply({"params": p}, x) * probe).sum(),
            argnums=(0, 1))(p, x)
        want, gr = jax.value_and_grad(
            lambda p, x: (REF.attention(cfg, PREC, p, x) * probe).sum(),
            argnums=(0, 1))(p, x)
        out = layer.apply({"params": p}, x)
    assert rel(out, REF.attention(cfg, PREC, p, x)) < TOL
    assert abs(float(got - want)) < TOL * abs(float(want)) + 1e-4
    worst = max(jax.tree.leaves(jax.tree.map(rel, g, gr)))
    assert worst < TOL, worst
    # mu and phi take a gradient only where a summary is seen
    live = windows > 1
    assert (float(jnp.abs(g[0]["mu"]).max()) > 0) == live
    assert (float(jnp.abs(g[0]["phi"]).max()) > 0) == live


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_row_of_one_window_is_plain_causal_attention(impl):
    q, k, v, mu, phi = qkv(WINDOW)
    with jax.default_matmul_precision("highest"):
        got = eva(q, k, v, mu, phi, impl)
        want = dot_product_attention(q, k, v, causal=True)
    assert rel(got, want) < TOL


# -- what a query sees ----------------------------------------------------------

def test_the_gauge_counts_the_pairs_of_both_parts():
    assert eva_pairs(8192, 2048, 16) == {"local": 8392704, "summary": 1572864}
    q, k, v, mu, phi = qkv(4 * WINDOW)
    eva(q, k, v, mu, phi)
    reg = get_registry()
    per_window = WINDOW // CHUNK
    assert reg.value("fdtpu_eva_pairs", "local") == 4 * WINDOW * (WINDOW + 1) // 2
    # window w's queries see 8 w summaries each: 8 * 32 * (0 + 1 + 2 + 3)
    assert reg.value("fdtpu_eva_pairs", "summary") == per_window * WINDOW * 6


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_first_query_of_window_w_sees_8w_summaries_and_window_0_none(impl):
    """A one-hot probe of ``v``: with ``v_m = e_m`` and ``phi = 0`` (a
    chunk's value is then the mean of its positions' values) the output
    at ``t`` holds, at feature ``m``, the weight ``t`` gives position
    ``m``: directly, or a quarter of the weight of ``m``'s summary."""
    t, h = 4 * WINDOW, 1
    q, k, _, mu, _ = qkv(t, h=h, d=t, b=1, seed=3)
    v = jnp.eye(t)[None, :, None, :]
    phi = jnp.zeros((h, t))
    with jax.default_matmul_precision("highest"):
        out = eva(q, k, v, mu, phi, impl)[0, :, 0]  # [query, position]
    seen = np.asarray(out) > 0
    for w in range(4):
        first = w * WINDOW
        # its own position, and whole chunks of every earlier window
        assert seen[first].sum() == 1 + w * WINDOW
        assert seen[first, :first].all() and seen[first, first]
        chunks = np.asarray(out[first, :first]).reshape(-1, CHUNK)
        assert len(chunks) == (WINDOW // CHUNK) * w
        assert np.allclose(chunks, chunks[:, :1])  # one weight a chunk
    last0 = WINDOW - 1
    assert seen[last0, :WINDOW].all() and not seen[last0, WINDOW:].any()
    np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("at", [CHUNK, 2 * CHUNK - 1, WINDOW - 1, WINDOW,
                                2 * WINDOW + CHUNK])
def test_a_change_at_t_moves_nothing_before_t(impl, at):
    """At a chunk's first and last position and on both sides of a
    window's edge: the change moves its own chunk's summary, which no
    query before the next window sees."""
    cfg, kw = tiny(seq_len=3 * WINDOW)
    layer = EvaAttention(EvaByteConfig(**fd.models.common.json_kwargs(
        dict(kw, attention_impl=impl), "heads_held")))
    p = REF.make_params(cfg, jax.random.PRNGKey(7))[0]["layer0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3 * WINDOW, 32))
    a = layer.apply({"params": p}, x)
    b = layer.apply({"params": p}, x.at[:, at].add(1.0))
    assert float(jnp.abs(a[:, :at] - b[:, :at]).max()) == 0.0
    assert float(jnp.abs(a[:, at:] - b[:, at:]).min(axis=-1).max()) > 1e-4


def test_the_lse_merge_is_the_softmax_over_the_concatenation():
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    b, t, h, d, n1, n2 = 2, 8, 2, 4, 6, 3
    s1 = jax.random.normal(ks[0], (b, h, t, n1))
    s2 = jax.random.normal(ks[1], (b, h, t, n2)) + 2.0
    v1 = jax.random.normal(ks[2], (b, n1, h, d))
    v2 = jax.random.normal(ks[3], (b, n2, h, d))
    part = lambda s, v: (  # noqa: E731
        jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v),
        jax.nn.logsumexp(s, -1))
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.concatenate([s1, s2], -1), -1),
                      jnp.concatenate([v1, v2], 1))
    assert rel(merge_by_lse(*part(s1, v1), *part(s2, v2)), want) < 1e-6
    # an empty second part, as flash_attention_lse reports a row that
    # attends nothing: its weight is exactly nought, whatever its output
    o1, l1 = part(s1, v1)
    empty = merge_by_lse(o1, l1, jnp.full_like(o1, 7.0),
                         jnp.full_like(l1, -1e30))
    assert float(jnp.abs(empty - o1).max()) == 0.0


def test_chunk_summaries_pool_keys_by_mu_and_values_by_phi():
    _, k, v, mu, phi = qkv(2 * CHUNK, b=1)
    ksum, vsum = chunk_summaries(k, v, mu, phi, CHUNK)
    for c in range(2):
        kc, vc = (x[0, c * CHUNK:(c + 1) * CHUNK] for x in (k, v))  # [m, h, d]
        wk = jax.nn.softmax(jnp.einsum("mhd,hd->mh", kc, mu), axis=0)
        wv = jax.nn.softmax(jnp.einsum("mhd,hd->mh", kc, phi), axis=0)
        np.testing.assert_allclose(ksum[0, c], jnp.einsum("mh,mhd->hd", wk, kc),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(vsum[0, c], jnp.einsum("mh,mhd->hd", wv, vc),
                                   rtol=1e-5, atol=1e-6)


# -- the share --------------------------------------------------------------------

def test_the_four_head_shares_add_up_to_the_uncut_layer():
    """Heads 0-1, 2-3, 4-5, 6-7 of 8, each through its own columns of
    ``W_q``, ``W_k``, ``W_v``, its ``mu``, ``phi`` and its rows of
    ``W_o``: the program's four partial results sum to the reference's
    8-head layer."""
    cfg, kw = tiny(seq_len=2 * WINDOW, heads=8, held=(0, 8))
    p = REF.make_params(cfg, jax.random.PRNGKey(11))[0]["layer0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 2 * WINDOW, 32))
    with jax.default_matmul_precision("highest"):
        whole = REF.attention(cfg, PREC, p, x)
        total = 0.0
        for first in (0, 2, 4, 6):
            take = slice(first, first + 2)
            share = {n: {"kernel": p[n]["kernel"][:, take]} for n in "qkv"}
            share["out"] = {"kernel": p["out"]["kernel"][take]}
            share["mu"], share["phi"] = p["mu"][take], p["phi"][take]
            layer = EvaAttention(EvaByteConfig(**fd.models.common.json_kwargs(
                dict(kw, heads_held=[first, 2]), "heads_held")))
            part = layer.apply({"params": share}, x)
            # and the reference given the same share agrees with it
            ref_part = REF.attention(dict(cfg, heads_held=[first, 2]),
                                     PREC, share, x)
            assert rel(part, ref_part) < TOL
            total = total + part
    assert rel(total, whole) < TOL
    assert rel(part, whole) > 0.1  # one share alone is not the layer


# -- norm, stream, loss -------------------------------------------------------------

def test_rms_norm_with_the_unit_offset():
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
    norm = rms_norm(jnp.float32, 1e-5, "n", unit_offset=True)
    params = norm.init(jax.random.PRNGKey(1), x)["params"]
    assert float(jnp.abs(params["scale"]).max()) == 0.0  # the weight is 1 + 0
    w = 0.1 * jnp.arange(16.0)
    got = norm.apply({"params": {"scale": w}}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(
        got, REF._rms({"rms_norm_eps": 1e-5}, PREC, x, w), rtol=1e-6)
    # without the offset the same weights scale by w alone
    plain = rms_norm(jnp.float32, 1e-5, "n").apply({"params": {"scale": w}}, x)
    assert float(jnp.abs(plain - got).max()) > 0.1


def _model_loss_and_grads(cfg, kw, params, tokens):
    model = fd.models.evabyte(**kw)
    loss_fn = fd.models.lm_loss_fn(model)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss_fn(p, {}, {"tokens": tokens}, True)[0])(params)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_model_matches_the_reference_and_the_stream_is_float32(impl):
    cfg, kw = tiny(seq_len=2 * WINDOW, held=(1, 2), attention_impl=impl)
    params, state = REF.make_params(cfg, jax.random.PRNGKey(3))
    assert state == {}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 2 * WINDOW), 0, 32)
    with jax.default_matmul_precision("highest"):
        want, gr = jax.value_and_grad(lambda p: REF.row_loss_sum(
            cfg, PREC, p, {}, tokens)[0] / 2)(params)
    got, g = _model_loss_and_grads(cfg, kw, params, tokens)
    assert abs(float(got - want)) < TOL * float(want)
    assert max(jax.tree.leaves(jax.tree.map(rel, g, gr))) < TOL
    if impl == "xla":
        # bfloat16 in the stream's place, all else float32 (the reference's
        # layer with each residual sum rounded): the gradients are out of
        # the tolerance by two orders, so the program's stream is float32
        def rounded_stream(p):
            r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
            x = r(p["embed"]["embedding"][tokens])
            for i in range(cfg["num_hidden_layers"]):
                q = p[f"layer{i}"]
                x = r(x + REF.attention(cfg, PREC, q["attn"], REF._rms(
                    cfg, PREC, x, q["attn_norm"]["scale"])))
                x = r(x + REF.feed_forward(PREC, q["mlp"], REF._rms(
                    cfg, PREC, x, q["ffn_norm"]["scale"])))
            x = REF._rms(cfg, PREC, x, p["final_norm"]["scale"])
            logits = PREC.einsum("btd,dv->btv", x, p["lm_head"]["kernel"])
            return jnp.mean(REF.heads_loss(
                cfg, logits.reshape(*logits.shape[:2], 8, -1), tokens))
        with jax.default_matmul_precision("highest"):
            gl = jax.grad(rounded_stream)(params)
        assert max(jax.tree.leaves(jax.tree.map(rel, gl, g))) > 100 * TOL


def test_the_eight_headed_loss_is_the_references_to_a_rows_last_positions():
    """Output ``i`` at ``t`` scores byte ``t + 1 + i`` wherever there is
    one: head ``i`` has ``T - 1 - i`` terms, so the row's last 8
    positions are where the heads differ."""
    cfg, kw = tiny(seq_len=WINDOW)
    t = WINDOW
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, t, 8, 32))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, t), 0, 32)
    by_hand = 0.0
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    for i in range(8):
        terms = [[-logp[r, s, i, int(tokens[r, s + 1 + i])]
                  for s in range(t) if s + 1 + i < t] for r in range(2)]
        assert len(terms[0]) == t - 1 - i
        by_hand += np.mean(terms, axis=1)
    np.testing.assert_allclose(REF.heads_loss(cfg, logits, tokens), by_hand,
                               rtol=1e-5)
    # the program: next_token_loss of output 0 plus mtp_weight (7) times
    # the mean of the seven sown terms
    params, _ = REF.make_params(cfg, jax.random.PRNGKey(3))
    model = fd.models.evabyte(**kw)
    assert model.mtp_weight == 7
    _, sown = model.apply({"params": params}, tokens, train=True,
                          mutable=["losses"])
    assert sorted(sown["losses"]) == [f"mtp_loss{j}" for j in range(7)]
    got = _model_loss_and_grads(cfg, kw, params, tokens)[0]
    want = REF.row_loss_sum(cfg, PREC, params, {}, tokens)[0] / 2
    assert abs(float(got - want)) < 1e-4 * float(want)
    # evaluation sows nothing and returns output 0 alone
    logits, sown = model.apply({"params": params}, tokens, train=False,
                               mutable=["losses"])
    assert logits.shape == (2, t, 32) and not sown.get("losses")


# -- refusals, sizes ----------------------------------------------------------------

def test_a_row_that_is_no_multiple_of_the_window_raises():
    kw = full_config()["model"]["kwargs"]
    model = fd.models.evabyte(**dict(kw, num_layers=1))
    with pytest.raises(ValueError, match="3000 positions is no multiple of "
                                         "window_size"):
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 3000), jnp.int32)))
    with pytest.raises(ValueError, match="no multiple"):
        q, k, v, mu, phi = qkv(WINDOW + CHUNK)
        eva(q, k, v, mu, phi)
    with pytest.raises(ValueError, match="heads_held"):
        fd.models.evabyte(**dict(kw, heads_held=[30, 4]))
    with pytest.raises(ValueError, match="attention_impl"):
        eva(*qkv(WINDOW), impl="flash")


def test_serving_is_refused_with_one_message():
    kw = full_config()["model"]["kwargs"]
    with pytest.raises(NotImplementedError, match="a window's keys"):
        fd.models.EvaByte(EvaByteConfig(), decode=True)
    from fluxdistributed_tpu.serve.engine import LMEngine

    with pytest.raises(NotImplementedError) as e:
        LMEngine(fd.models.evabyte(**kw), params=None)
    assert str(e.value) == NO_DECODE


@pytest.mark.parametrize("held,layers,count", [
    ([0, 8], 4, 620015616),       # the cell: 8 of 32 heads, 4 layers
    ([0, 4], 4, 586457088),       # the issue's fallback cut
    ([0, 32], 32, 6488330240),    # the published model
])
def test_parameter_counts(held, layers, count):
    cfg = full_config()
    kw = dict(cfg["model"]["kwargs"], heads_held=held, num_layers=layers)
    model = fd.models.evabyte(**kw)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2048), jnp.int32)))["params"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == count
    ref_cfg = dict(cfg, heads_held=held, num_hidden_layers=layers)
    assert sum(int(np.prod(s)) for s, _ in
               REF.param_shapes(ref_cfg).values()) == count
    if held == [0, 8]:
        assert cfg["parameters"] == count
        # the program's tree is the reference's, name for name
        made = jax.eval_shape(lambda k: REF.make_params(cfg, k)[0],
                              jax.random.PRNGKey(0))
        assert (jax.tree.map(lambda x: x.shape, made)
                == jax.tree.map(lambda x: x.shape, shapes))


def test_forward_macs_is_the_hand_count():
    cfg = full_config()
    t = 8192
    layer = 4 * 4096 * 1024 + 3 * 4096 * 11008      # per token
    pairs = 9965568 * 2 * 128 * 8                   # per row and layer
    pooling = t * 8 * 4 * 128
    want = 4 * (t * layer + pairs + pooling) + t * 4096 * 8 * 320
    assert REF.forward_macs(cfg) == want
    assert round(want / t / 1e6, 1) == 628.6        # a token, as the issue has it
