"""Plain reference for the ``glm47_flash`` configuration: GLM-4.7-Flash
(``model_type: glm4_moe_lite``) under its training loss, as far as its
public ``config.json`` states it.  Straightforward ``jax.numpy``,
float32; nothing here imports the program, and the parameter tree only
carries the names the program's tree has.

One layer, ``x`` of ``[rows, positions, hidden_size]``:

* latent attention: ``c_q = rms(x W_qa)``; ``q = c_q W_qb`` in heads of
  ``qk_nope_head_dim + qk_rope_head_dim``; ``[c_kv | k_r] = x W_kva``;
  ``c_kv = rms(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` per head; rotary
  positions on the last ``qk_rope_head_dim`` features of ``q`` and on
  ``k_r``, which every head shares; ``k = [k_nope | k_r]``; causal
  softmax of ``q k^T / sqrt(qk_nope + qk_rope)``; times ``v``; ``W_o``.
* a dense SwiGLU in the first ``first_k_dense_replace`` layers; after
  them ``s = sigmoid(x W_r)`` in float32 over all ``router_experts``,
  the ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` a bias
  that is no trained parameter), their weights ``s`` (without ``b``)
  over the sum of the chosen times ``routed_scaling_factor``, and
  ``y = sum_i g_i E_i(x) + E_shared(x)``, ``E(x) = W_down(silu(W_gate x)
  * W_up x)``.  After a step ``b_i += gamma sign(mean(c) - c_i)``, ``c``
  the step's count of token-slots an expert over the whole batch
  (``merge_state``).
* the multi-token module (``num_nextn_predict_layers``): ``h' = W_eh
  [rms_h(h_i) ; rms_e(Emb(t_{i+1}))]``, one more expert layer, its own
  final norm, the shared head, cross-entropy against ``t_{i+2}``;
  ``loss = L_next + mtp_weight L_mtp``.

Departures and silences, each also under ``assumed`` in the ``.json``:
the config does not state the scoring function (sigmoid, as its sibling
GLM-5 states and ``noaux_tc`` implies), the rotary layout (neighbouring
pairs here), ``gamma``, ``mtp_weight``, nor the order inside ``W_eh``'s
input.  ``h_i`` is the trunk's output before its final norm.

The chip's share: ``experts_held = [first, count]`` of the
``router_experts`` live here.  The router keeps its width and its
experts a token, the weights are normalised over all chosen experts, and
what the absent experts would add is left out; with ``[0,
router_experts]`` this file is the uncut layer.  The vocabulary is the
configuration's ``input.vocab``, a slice where the file says so.

For memory only: a row and, within it, each layer are rematerialised
(``jax.checkpoint``), attention runs over blocks of query rows, each
against every key, and the rows of a block run one after another.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows an attention block; memory only


def _sizes(cfg):
    first, count = cfg["experts_held"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        rq=cfg["q_lora_rank"], rkv=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], dense=cfg["intermediate_size"],
        m=cfg["moe_intermediate_size"], e=cfg["router_experts"],
        first=first, held=count, k=cfg["num_experts_per_tok"],
        v=cfg["input"]["vocab"], layers=cfg["num_hidden_layers"],
        lead=cfg["first_k_dense_replace"],
        mtp=cfg["num_nextn_predict_layers"])


def _layer_shapes(z, dense: bool):
    d, h = z["d"], z["h"]
    s = {
        ("attn_norm", "scale"): ((d,), "one"),
        ("mlp_norm", "scale"): ((d,), "one"),
        ("attn", "q_a", "kernel"): ((d, z["rq"]), d),
        ("attn", "q_a_norm", "scale"): ((z["rq"],), "one"),
        ("attn", "q_b", "kernel"): ((z["rq"], h, z["nope"] + z["rope"]), z["rq"]),
        ("attn", "kv_a", "kernel"): ((d, z["rkv"] + z["rope"]), d),
        ("attn", "kv_a_norm", "scale"): ((z["rkv"],), "one"),
        ("attn", "kv_b", "kernel"): ((z["rkv"], h, z["nope"] + z["dv"]), z["rkv"]),
        ("attn", "o", "kernel"): ((h, z["dv"], d), h * z["dv"]),
    }
    if dense:
        width = z["dense"]
        s[("mlp", "gate", "kernel")] = ((d, width), d)
        s[("mlp", "up", "kernel")] = ((d, width), d)
        s[("mlp", "down", "kernel")] = ((width, d), width)
    else:
        m = z["m"]
        s[("moe", "router")] = ((d, z["e"]), d)
        s[("moe", "w_gate")] = ((z["held"], d, m), d)
        s[("moe", "w_up")] = ((z["held"], d, m), d)
        s[("moe", "w_down")] = ((z["held"], m, d), m)
        s[("shared", "gate", "kernel")] = ((d, m), d)
        s[("shared", "up", "kernel")] = ((d, m), d)
        s[("shared", "down", "kernel")] = ((m, d), m)
    return s


def param_shapes(cfg):
    z = _sizes(cfg)
    d = z["d"]
    s = {
        ("embed", "embedding"): ((z["v"], d), "unit"),
        ("final_norm", "scale"): ((d,), "one"),
        ("head", "kernel"): ((d, z["v"]), d),
    }
    for i in range(z["layers"]):
        for path, v in _layer_shapes(z, i < z["lead"]).items():
            s[(f"layer{i}",) + path] = v
    for j in range(z["mtp"]):
        top = f"mtp{j}"
        s[(top, "hnorm", "scale")] = ((d,), "one")
        s[(top, "enorm", "scale")] = ((d,), "one")
        s[(top, "eh_proj", "kernel")] = ((2 * d, d), 2 * d)
        s[(top, "final_norm", "scale")] = ((d,), "one")
        for path, v in _layer_shapes(z, False).items():
            s[(top, "block") + path] = v
    return s


def _expert_layers(cfg):
    """The top-level paths of every layer that has a router."""
    z = _sizes(cfg)
    return ([(f"layer{i}",) for i in range(z["lead"], z["layers"])]
            + [(f"mtp{j}", "block") for j in range(z["mtp"])])


def _nest(flat):
    tree = {}
    for path, v in flat.items():
        d = tree
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return tree


def make_params(cfg, key):
    """Seeded weights: every kernel and the router normal with standard
    deviation 1/sqrt(fan-in), the embedding normal(1), norms 1.  The
    model state is the routers': a selection bias of nought and a load
    of nought for every one of the ``router_experts``."""
    flat = {}
    for i, (path, (shape, kind)) in enumerate(sorted(param_shapes(cfg).items())):
        if kind == "one":
            flat[path] = jnp.ones(shape, jnp.float32)
        else:
            std = 1.0 if kind == "unit" else kind ** -0.5
            flat[path] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    e = cfg["router_experts"]
    state = {}
    for path in _expert_layers(cfg):
        for leaf in ("bias", "load"):
            state[path + ("moe", leaf)] = jnp.zeros((e,), jnp.float32)
    return _nest(flat), ({"router": _nest(state)} if state else {})


# -- the layer ---------------------------------------------------------------

def _rms(cfg, prec, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return prec.store(x * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * scale)


def _rope(cfg, x):
    """``x``: [rows, positions, heads, features]; feature 2i is rotated
    with feature 2i+1 by position / theta^(2i/features)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attention(cfg, prec, p, x):
    """Latent attention on ``x`` [rows, positions, hidden]."""
    z = _sizes(cfg)
    nope, t = z["nope"], x.shape[1]
    c_q = _rms(cfg, prec, prec.einsum("btd,dr->btr", x, p["q_a"]["kernel"]),
               p["q_a_norm"]["scale"])
    q = prec.store(prec.einsum("btr,rhf->bthf", c_q, p["q_b"]["kernel"]))
    kv = prec.store(prec.einsum("btd,dr->btr", x, p["kv_a"]["kernel"]))
    c_kv = _rms(cfg, prec, kv[..., :z["rkv"]], p["kv_a_norm"]["scale"])
    k_r = prec.store(_rope(cfg, kv[..., z["rkv"]:][:, :, None, :]))
    kn_v = prec.store(prec.einsum("btr,rhf->bthf", c_kv, p["kv_b"]["kernel"]))
    k_nope, v = kn_v[..., :nope], kn_v[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], prec.store(_rope(cfg, q[..., nope:]))], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, k_nope.shape[:-1] + (z["rope"],))],
        axis=-1)
    q = prec.store(q / jnp.sqrt(jnp.float32(q.shape[-1])))

    @jax.checkpoint
    def rows(args):
        q_blk, first = args
        s = prec.einsum("bqhf,bkhf->bhqk", q_blk, k)
        q_pos = first + jnp.arange(q_blk.shape[1])
        seen = jnp.arange(t)[None, :] <= q_pos[:, None]
        s = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return prec.einsum("bhqk,bkhf->bqhf", s, v)

    blk = min(Q_BLOCK, t)
    if t % blk:
        blk = t
    n = t // blk
    q_blocks = q.reshape(q.shape[0], n, blk, *q.shape[2:]).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(rows, (q_blocks, jnp.arange(n) * blk))
    out = prec.store(out.transpose(1, 0, 2, 3, 4).reshape(
        q.shape[0], t, z["h"], z["dv"]))
    return prec.store(prec.einsum("bthf,hfd->btd", out, p["o"]["kernel"]))


def _swiglu(prec, x, gate, up, down):
    g = prec.store(prec.einsum("...d,dm->...m", x, gate))
    u = prec.store(prec.einsum("...d,dm->...m", x, up))
    return prec.store(prec.einsum(
        "...m,md->...d", prec.store(jax.nn.silu(g) * u), down))


def route(cfg, x, router, bias):
    """``(chosen [.., k], weights [.., k], load [experts])``: float32
    throughout, whatever the precision of the rest."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), router, precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    e = router.shape[-1]
    load = jnp.sum(chosen[..., None] == jnp.arange(e), axis=tuple(
        range(chosen.ndim))).astype(jnp.float32)
    return chosen, w, load


def routed_part(cfg, prec, p, x, chosen, w):
    """What the experts held here add: each of them over every token,
    times the token's weight for it, nought where it was not chosen."""
    first, count = cfg["experts_held"]
    held = first + jnp.arange(count)
    weight = jnp.sum(jnp.where(chosen[..., None, :] == held[:, None],
                               w[..., None, :], 0.0), axis=-1)  # [.., held]
    g = prec.store(prec.einsum("...d,edm->...em", x, p["w_gate"]))
    u = prec.store(prec.einsum("...d,edm->...em", x, p["w_up"]))
    out = prec.store(prec.einsum(
        "...em,emd->...ed", prec.store(jax.nn.silu(g) * u), p["w_down"]))
    return prec.store(jnp.einsum("...ed,...e->...d", out, weight,
                                 precision=HIGHEST))


def expert_mlp(cfg, prec, p, state, x):
    chosen, w, load = route(cfg, x, p["moe"]["router"],
                            state["moe"]["bias"])
    y = routed_part(cfg, prec, p["moe"], x, chosen, w)
    s = p["shared"]
    y = y + _swiglu(prec, x, s["gate"]["kernel"], s["up"]["kernel"],
                    s["down"]["kernel"])
    return prec.store(y), {"moe": {"bias": state["moe"]["bias"], "load": load}}


def layer(cfg, prec, p, state, x):
    """One layer; ``state`` is its router's, None for a dense layer.
    Returns the new hidden state and the layer's state with this
    block's load in it."""
    x = prec.store(x + attention(
        cfg, prec, p["attn"], _rms(cfg, prec, x, p["attn_norm"]["scale"])))
    y = _rms(cfg, prec, x, p["mlp_norm"]["scale"])
    if state is None:
        m = p["mlp"]
        out, new = _swiglu(prec, y, m["gate"]["kernel"], m["up"]["kernel"],
                           m["down"]["kernel"]), None
    else:
        out, new = expert_mlp(cfg, prec, p, state, y)
    return prec.store(x + out), new


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _next_token_rows(prec, x, head, targets):
    """Per row, the mean over its targets of -log softmax(x head)."""
    logits = prec.store(prec.einsum("btd,dv->btv", x, head))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll, axis=-1)


def _one_row(cfg, prec, params, routers, tokens):
    """One block of rows through the model: the sum of the rows' losses
    and each router's load over these rows, by the router's path."""
    z = _sizes(cfg)
    loads = {}
    x = prec.store(params["embed"]["embedding"][tokens])

    def run(path, x):
        p = _get(params, path)
        state = None if "mlp" in p else _get(routers, path)
        x, out = jax.checkpoint(
            lambda p, s, x: layer(cfg, prec, p, s, x))(p, state, x)
        if out is not None:
            loads[path] = out["moe"]["load"]
        return x

    for i in range(z["layers"]):
        x = run((f"layer{i}",), x)
    head = params["head"]["kernel"]
    total = jnp.sum(_next_token_rows(
        prec, _rms(cfg, prec, x, params["final_norm"]["scale"])[:, :-1],
        head, tokens[:, 1:]))
    h = x
    for j in range(z["mtp"]):
        p = params[f"mtp{j}"]
        emb = prec.store(params["embed"]["embedding"][tokens[:, j + 1:]])
        both = jnp.concatenate(
            [_rms(cfg, prec, h[:, :emb.shape[1]], p["hnorm"]["scale"]),
             _rms(cfg, prec, emb, p["enorm"]["scale"])], axis=-1)
        h = prec.store(prec.einsum("btd,df->btf", both, p["eh_proj"]["kernel"]))
        h = run((f"mtp{j}", "block"), h)
        out = _rms(cfg, prec, h, p["final_norm"]["scale"])
        total = total + cfg["mtp_weight"] * jnp.sum(_next_token_rows(
            prec, out[:, :-1], head, tokens[:, j + 2:]))
    return total, loads


def row_loss_sum(cfg, prec, params, model_state, tokens):
    """The sum over the block's rows of each row's loss: the mean over
    its ``seq_len - 1`` targets of the next-token term plus
    ``mtp_weight`` times the mean over its ``seq_len - 2`` targets of
    the multi-token term.  The state handed back holds this block's
    load; ``merge_state`` makes the step's state of the blocks'.

    For memory only, the rows of a block run one after another
    (``jax.lax.map`` over rematerialised rows): nothing spans two rows
    but the sums taken here."""
    routers = model_state.get("router", {})
    totals, loads = jax.lax.map(
        jax.checkpoint(lambda row: _one_row(cfg, prec, params, routers,
                                            row[None])), tokens)
    state = {}
    for path, load in loads.items():
        state[path + ("moe", "bias")] = _get(routers, path)["moe"]["bias"]
        state[path + ("moe", "load")] = jnp.sum(load, axis=0)
    return jnp.sum(totals), ({"router": _nest(state)} if state else {})


def merge_state(cfg, model_state, states, rows):
    """The step's state from its blocks': an expert's load is the sum
    of the blocks' loads, and its selection bias moves by
    ``bias_update_rate`` towards the mean load."""
    if not model_state:
        return model_state
    gamma = cfg["bias_update_rate"]

    def merged(old, *blocks):
        load = sum(b["load"] for b in blocks)
        bias = old["bias"] + gamma * jnp.sign(jnp.mean(load) - load)
        return {"bias": bias, "load": load}

    is_router = lambda x: isinstance(x, dict) and set(x) == {"bias", "load"}  # noqa: E731
    return jax.tree.map(merged, model_state, *states, is_leaf=is_router)


# The rows' losses are independent and the routers' state is merged over
# the blocks, so a block could be one row.  It is a step's four: the
# harness keeps a block's gradient while it computes the next block's,
# so from the third block on seven float32 copies of the parameters
# (weights, their start, two moments, the sum, the last block's, the
# new one's; 7 x 2.37 GB) would be on the chip beside a row's
# activations, and 16.9 GB do not hold them (my chip run, PR 27).  One
# block a step holds five copies, the gradient and one row's work.
ROW_BLOCK = 4


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass: per layer
    the latent attention's five projections and its two products over
    the keys a causal query may see; the dense SwiGLU, or the router,
    the shared expert and the routed experts held here at the share of a
    token's ``num_experts_per_tok`` that is expected to fall on them;
    the head; the multi-token modules with their projection and head.
    The embedding is a gather."""
    z = _sizes(cfg)
    t, d, h = cfg["input"]["seq_len"], z["d"], z["h"]
    qk, dv = z["nope"] + z["rope"], z["dv"]
    proj = (d * z["rq"] + z["rq"] * h * qk + d * (z["rkv"] + z["rope"])
            + z["rkv"] * h * (z["nope"] + dv) + h * dv * d)

    def attn(n):
        return n * proj + (n * (n + 1) // 2) * h * (qk + dv)

    expert = 3 * d * z["m"]
    held_share = z["k"] * z["held"] / z["e"]
    sparse = d * z["e"] + expert + held_share * expert
    dense = 3 * d * z["dense"]
    total = 0
    for i in range(z["layers"]):
        total += attn(t) + t * (dense if i < z["lead"] else sparse)
    total += t * d * z["v"]
    for j in range(z["mtp"]):
        n = t - j - 1
        total += n * 2 * d * d + attn(n) + n * sparse + n * d * z["v"]
    return int(total)


# the layer the loss reads
HEAD = ("head",)
