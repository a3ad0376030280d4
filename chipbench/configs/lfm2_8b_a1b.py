"""Plain reference for the ``lfm2_8b_a1b`` configuration: LFM2-8B-A1B
(``model_type: lfm2_moe``) under its training loss, as far as its public
``config.json`` and the family's published implementation state it.
Straightforward ``jax.numpy``, float32; nothing here imports the
program, and the parameter tree only carries the names the program's
tree has.

One layer, ``x`` of ``[rows, positions, hidden_size]``: ``h = x +
Op(rms_op(x))``; ``y = h + FF(rms_ff(h))``.  ``Op`` by ``layer_types[i]``:

* ``conv``, the gated short convolution: ``[B | C | z] = x W_in`` (in
  that order); ``u = B * z``; ``c_t = sum_j w_j * u_{t - (L - 1) + j}``
  with ``w`` of ``[hidden_size, conv_L_cache]``, one filter a feature,
  ``u`` nought before the row's first position (depthwise, causal, no
  bias); ``Op = (C * c) W_out``.  No activation, no recurrence.
* ``full_attention``: ``q = x W_q`` in ``num_attention_heads`` heads,
  ``k = x W_k``, ``v = x W_v`` in ``num_key_value_heads``, no bias;
  ``q = rms_q(q)``, ``k = rms_k(k)`` over each head's features (one
  weight for all query heads, one for all key heads); rotary positions
  on all features of ``q`` and ``k``; causal softmax of ``q k^T /
  sqrt(head)``, a group of query heads to a key-value head; ``W_o``.

``FF`` is a dense SwiGLU in the first ``num_dense_layers`` layers; after
them ``s = sigmoid(x W_r)`` in float32 over all ``router_experts``, the
``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` the expert
bias, no trained parameter), their weights ``s`` (without ``b``) over
the sum of the chosen times ``routed_scaling_factor``, and ``FF(x) =
sum_i g_i E_i(x)``, ``E(x) = W_down(silu(W_gate x) * W_up x)``; no shared
expert.  After a step ``b_i += gamma sign(mean(c) - c_i)``, ``c`` the
step's count of token-slots an expert over the whole batch
(``merge_state``).  After the last layer one more ``rms`` (the published
model's ``embedding_norm``), then the head, the embedding's transpose.

Departures and silences, each also under ``assumed`` in the ``.json``:
the config names no per-head norm of ``q`` and ``k`` (the family's
implementation has it) and no tied head (the published parameter count
needs it); the implementation rotates halves, this file and the
program's rotary helper neighbouring pairs, which seeded weights cannot
tell apart; ``gamma``, the rule of ``b`` and the optimizer are the GLM
cell's.

The chip's share: ``experts_held = [first, count]`` of the
``router_experts`` live here.  The router keeps its width and its
experts a token, the weights are normalised over all chosen experts, and
what the absent experts would add is left out; with ``[0,
router_experts]`` this file is the uncut layer.  The vocabulary is the
configuration's ``input.vocab``, a slice where the file says so.

For memory only: a row and, within it, each layer are rematerialised
(``jax.checkpoint``), attention runs over blocks of query rows, each
against every key, the held experts run one after another, and the rows
of a block run one after another.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows an attention block; memory only


def _sizes(cfg):
    first, count = cfg["experts_held"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        d=d, h=h, hkv=cfg["num_key_value_heads"], hd=d // h,
        taps=cfg["conv_L_cache"], dense=cfg["intermediate_size"],
        m=cfg["moe_intermediate_size"], e=cfg["router_experts"],
        first=first, held=count, k=cfg["num_experts_per_tok"],
        v=cfg["input"]["vocab"], kinds=tuple(cfg["layer_types"]),
        lead=cfg["num_dense_layers"])


def _layer_shapes(z, kind: str, dense: bool):
    d, h, hkv, hd = z["d"], z["h"], z["hkv"], z["hd"]
    s = {("operator_norm", "scale"): ((d,), "one"),
         ("ffn_norm", "scale"): ((d,), "one")}
    if kind == "conv":
        s[("conv", "in_proj", "kernel")] = ((d, 3 * d), d)
        s[("conv", "filter")] = ((d, z["taps"]), z["taps"])
        s[("conv", "out_proj", "kernel")] = ((d, d), d)
    else:
        s[("attn", "q", "kernel")] = ((d, h, hd), d)
        s[("attn", "k", "kernel")] = ((d, hkv, hd), d)
        s[("attn", "v", "kernel")] = ((d, hkv, hd), d)
        s[("attn", "q_norm", "scale")] = ((hd,), "one")
        s[("attn", "k_norm", "scale")] = ((hd,), "one")
        s[("attn", "out", "kernel")] = ((h, hd, d), h * hd)
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
    return s


def param_shapes(cfg):
    z = _sizes(cfg)
    d = z["d"]
    # the head is the embedding's transpose: one leaf, of fan-in d
    s = {("embed", "embedding"): ((z["v"], d), d),
         ("final_norm", "scale"): ((d,), "one")}
    for i, kind in enumerate(z["kinds"]):
        for path, v in _layer_shapes(z, kind, i < z["lead"]).items():
            s[(f"layer{i}",) + path] = v
    return s


def _expert_layers(cfg):
    z = _sizes(cfg)
    return [f"layer{i}" for i in range(z["lead"], len(z["kinds"]))]


def _nest(flat):
    tree = {}
    for path, v in flat.items():
        d = tree
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return tree


def make_params(cfg, key):
    """Seeded weights: every kernel, the router, the filters and the
    tied embedding normal with standard deviation 1/sqrt(fan-in) (the
    embedding's fan-in is that of the head it also is), norms 1.  The
    model state is the routers': an expert bias of nought and a load of
    nought for every one of the ``router_experts``."""
    flat = {}
    for i, (path, (shape, kind)) in enumerate(sorted(param_shapes(cfg).items())):
        if kind == "one":
            flat[path] = jnp.ones(shape, jnp.float32)
        else:
            flat[path] = kind ** -0.5 * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    e = cfg["router_experts"]
    state = {(name, "moe", leaf): jnp.zeros((e,), jnp.float32)
             for name in _expert_layers(cfg) for leaf in ("bias", "load")}
    return _nest(flat), ({"router": _nest(state)} if state else {})


# -- the layer ---------------------------------------------------------------

def _rms(cfg, prec, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return prec.store(x * jax.lax.rsqrt(var + cfg["norm_eps"]) * scale)


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


def short_conv(cfg, prec, p, x):
    """The gated short convolution on ``x`` [rows, positions, hidden]."""
    d, t = x.shape[-1], x.shape[1]
    bcz = prec.store(prec.einsum("btd,de->bte", x, p["in_proj"]["kernel"]))
    b, c, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    u = b * z
    w = p["filter"]
    taps = w.shape[-1]
    conv = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads the input `back` positions ago
        conv = conv + w[:, j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return prec.store(prec.einsum("btd,de->bte", prec.store(c * conv),
                                  p["out_proj"]["kernel"]))


def attention(cfg, prec, p, x):
    """Grouped-query attention on ``x`` [rows, positions, hidden]."""
    z = _sizes(cfg)
    t, hkv, group = x.shape[1], z["hkv"], z["h"] // z["hkv"]
    q = prec.store(prec.einsum("btd,dhf->bthf", x, p["q"]["kernel"]))
    k = prec.store(prec.einsum("btd,dhf->bthf", x, p["k"]["kernel"]))
    v = prec.store(prec.einsum("btd,dhf->bthf", x, p["v"]["kernel"]))
    q = prec.store(_rope(cfg, _rms(cfg, prec, q, p["q_norm"]["scale"])))
    k = prec.store(_rope(cfg, _rms(cfg, prec, k, p["k_norm"]["scale"])))
    q = prec.store(q / jnp.sqrt(jnp.float32(z["hd"])))
    # query head h reads key-value head h // group
    q = q.reshape(q.shape[0], t, hkv, group, z["hd"])

    @jax.checkpoint
    def rows(args):
        q_blk, first = args
        s = prec.einsum("bqhgf,bkhf->bhgqk", q_blk, k)
        q_pos = first + jnp.arange(q_blk.shape[1])
        seen = jnp.arange(t)[None, :] <= q_pos[:, None]
        s = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return prec.einsum("bhgqk,bkhf->bqhgf", s, v)

    blk = min(Q_BLOCK, t)
    if t % blk:
        blk = t
    n = t // blk
    q_blocks = q.reshape(q.shape[0], n, blk, *q.shape[2:]).transpose(
        1, 0, 2, 3, 4, 5)
    out = jax.lax.map(rows, (q_blocks, jnp.arange(n) * blk))
    out = prec.store(out.transpose(1, 0, 2, 3, 4, 5).reshape(
        q.shape[0], t, z["h"], z["hd"]))
    return prec.store(prec.einsum("bthf,hfd->btd", out, p["out"]["kernel"]))


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
    times the token's weight for it, nought where it was not chosen.
    For memory only, one expert after another."""
    first, count = cfg["experts_held"]

    @jax.checkpoint
    def one(total, args):
        index, gate, up, down = args
        weight = jnp.sum(jnp.where(chosen == index, w, 0.0), axis=-1)
        out = _swiglu(prec, x, gate, up, down)
        return total + out * weight[..., None], None

    total, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32), (
        first + jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    return prec.store(total)


def expert_mlp(cfg, prec, p, state, x):
    chosen, w, load = route(cfg, x, p["moe"]["router"], state["moe"]["bias"])
    y = routed_part(cfg, prec, p["moe"], x, chosen, w)
    return y, {"moe": {"bias": state["moe"]["bias"], "load": load}}


def layer(cfg, prec, p, state, x):
    """One layer; ``state`` is its router's, None for a dense layer.
    Returns the new hidden state and the layer's state with this
    block's load in it."""
    y = _rms(cfg, prec, x, p["operator_norm"]["scale"])
    op = (short_conv(cfg, prec, p["conv"], y) if "conv" in p
          else attention(cfg, prec, p["attn"], y))
    x = prec.store(x + op)
    y = _rms(cfg, prec, x, p["ffn_norm"]["scale"])
    if state is None:
        m = p["mlp"]
        out, new = _swiglu(prec, y, m["gate"]["kernel"], m["up"]["kernel"],
                           m["down"]["kernel"]), None
    else:
        out, new = expert_mlp(cfg, prec, p, state, y)
    return prec.store(x + out), new


def _one_row(cfg, prec, params, routers, tokens):
    """One block of rows through the model: the sum of the rows' losses
    (per row, the mean over its targets of -log softmax) and each
    router's load over these rows, by the layer's name."""
    loads = {}
    table = params["embed"]["embedding"]
    x = prec.store(table[tokens])
    for i in range(len(cfg["layer_types"])):
        name = f"layer{i}"
        p = params[name]
        state = None if "mlp" in p else routers[name]
        x, out = jax.checkpoint(
            lambda p, s, x: layer(cfg, prec, p, s, x))(p, state, x)
        if out is not None:
            loads[name] = out["moe"]["load"]
    x = _rms(cfg, prec, x, params["final_norm"]["scale"])[:, :-1]
    logits = prec.store(prec.einsum("btd,vd->btv", x, table))
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(jnp.mean(nll, axis=-1)), loads


def row_loss_sum(cfg, prec, params, model_state, tokens):
    """The sum over the block's rows of each row's loss: the mean over
    its ``seq_len - 1`` targets of the next-token term.  The state handed
    back holds this block's load; ``merge_state`` makes the step's state
    of the blocks'.

    For memory only, the rows of a block run one after another
    (``jax.lax.map`` over rematerialised rows): nothing spans two rows
    but the sums taken here."""
    routers = model_state.get("router", {})
    totals, loads = jax.lax.map(
        jax.checkpoint(lambda row: _one_row(cfg, prec, params, routers,
                                            row[None])), tokens)
    state = {}
    for name, load in loads.items():
        state[(name, "moe", "bias")] = routers[name]["moe"]["bias"]
        state[(name, "moe", "load")] = jnp.sum(load, axis=0)
    return jnp.sum(totals), ({"router": _nest(state)} if state else {})


def merge_state(cfg, model_state, states, rows):
    """The step's state from its blocks': an expert's load is the sum
    of the blocks' loads, and its bias moves by ``bias_update_rate``
    towards the mean load."""
    if not model_state:
        return model_state
    gamma = cfg["bias_update_rate"]

    def merged(old, *blocks):
        load = sum(b["load"] for b in blocks)
        bias = old["bias"] + gamma * jnp.sign(jnp.mean(load) - load)
        return {"bias": bias, "load": load}

    is_router = lambda x: isinstance(x, dict) and set(x) == {"bias", "load"}  # noqa: E731
    return jax.tree.map(merged, model_state, *states, is_leaf=is_router)


# One block a step, as ``glm47_flash.py`` and for its reason: the harness
# keeps a block's gradient while it computes the next, seven float32
# copies of 606M parameters from a step's third block on.  One block
# holds five copies (12.1 GB), the gradient (2.4 GB) and one row's work.
ROW_BLOCK = 4


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass: per layer
    the short convolution's two projections and its taps, or the
    attention's four projections and its two products over the keys a
    causal query may see; the dense SwiGLU, or the router and the routed
    experts held here at the share of a token's ``num_experts_per_tok``
    that is expected to fall on them; the head.  The embedding is a
    gather."""
    z = _sizes(cfg)
    t, d, h, hd = cfg["input"]["seq_len"], z["d"], z["h"], z["hd"]
    conv = t * (4 * d * d + z["taps"] * d)
    attn = (t * (2 * d * h * hd + 2 * d * z["hkv"] * hd)
            + (t * (t + 1) // 2) * h * 2 * hd)
    sparse = d * z["e"] + (z["k"] * z["held"] / z["e"]) * 3 * d * z["m"]
    dense = 3 * d * z["dense"]
    total = t * d * z["v"]
    for i, kind in enumerate(z["kinds"]):
        total += conv if kind == "conv" else attn
        total += t * (dense if i < z["lead"] else sparse)
    return int(total)


# the layers the loss reads: the tied embedding (its gradient holds the
# head's use and the lookup's) and the norm before it
HEAD = ("embed", "final_norm")
