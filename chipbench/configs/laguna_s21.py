"""Plain reference for the ``laguna_s21`` configuration: poolside's
Laguna-S-2.1 (``model_type: laguna``) under its next-token loss, as far as
its public ``config.json`` states it.  Straightforward ``jax.numpy``,
float32; nothing here imports the program, and the parameter tree only
carries the names the program's tree has.

Each layer, ``x`` of ``[rows, T, hidden_size]``: ``h = x + Attn(rms(x))``,
``y = h + FF(rms(h))``, RMSNorm with a learned scale and eps
``rms_norm_eps``.  After the last layer one more RMSNorm, then an untied
head; the loss is the mean over positions of ``-log
softmax(logits[t])[x_{t+1}]``.  By published index ``l`` (``layer_offset``
plus the place in the stack):

* ``Attn``: ``q = x W_q`` in ``num_attention_heads_per_layer[l]`` heads,
  ``k = x W_k``, ``v = x W_v`` in ``num_key_value_heads``, each of
  ``head_dim`` features, no bias, no norm; rotary positions by
  ``rope_parameters[layer_types[l]]`` on the first ``partial_rotary_factor``
  of each head's features of ``q`` and ``k``: in a ``full_attention``
  layer YaRN's frequencies (``own_i = theta^(-2i/r)`` over the ``r``
  features that turn, ``own_i / factor`` above a linear ramp in ``i``
  from ``floor(c(beta_fast))`` to ``ceil(c(beta_slow))``, ``c(n) = r
  ln(original / (2 pi n)) / (2 ln theta)``, blended along it) and ``cos``
  and ``sin`` times ``attention_factor``; in a ``sliding_attention``
  layer the plain frequencies.  Softmax of ``q k^T / sqrt(head_dim)``
  over every earlier key (full) or over the ``sliding_window`` newest
  (sliding, the query's own among them), query head ``i`` reading
  key-value head ``i // group``; each head's output times ``sigmoid(x
  W_g)`` of that head (``gating: per-head``); ``W_o``.
* ``FF``: a dense SwiGLU of ``intermediate_size`` where ``l`` is in
  ``mlp_only_layers``; else ``s = sigmoid(x W_r)`` in float32 over all
  ``router_experts``, the ``num_experts_per_tok`` largest of ``s + b``
  chosen (``b`` the selection bias, no trained parameter), their weights
  ``s`` over the sum of the chosen times ``moe_routed_scaling_factor``,
  ``FF(x) = sum_i g_i E_i(x) + S(x)``, ``E`` and ``S`` (the shared expert
  of ``shared_expert_intermediate_size``) SwiGLUs ``W_down(silu(W_gate
  x) * W_up x)``.  After a step ``b_i += gamma sign(mean(c) - c_i)``,
  ``c`` the step's count of token-slots an expert (``merge_state``).

Departures and silences, each also under ``assumed`` in the ``.json``:
the score function (sigmoid), the rule of ``b`` and ``gamma``, the gate's
input (the normed input of the layer), no norm of ``q`` or ``k``, the
rotary layout (neighbouring pairs ``2i``, ``2i + 1`` of the features
that turn, where the published implementation may rotate halves: seeded
weights cannot tell the two apart), the optimizer.

The chip's share: ``experts_held = [first, count]`` of the
``router_experts`` live here; the router keeps its width and its experts
a token, and what the absent experts would add is left out.  With ``[0,
router_experts]`` this file is the uncut layer.  The vocabulary is the
configuration's ``input.vocab``, a slice where the file says so.

For memory only: each layer is rematerialised (``jax.checkpoint``); its
attention runs the rows one after another and, in a row, blocks of
``Q_BLOCK`` query rows against every key; the held experts run one
after another; the head runs over blocks of ``FF_BLOCK`` positions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256     # query rows an attention block; memory only
FF_BLOCK = 1024   # positions a head block; memory only


def _sizes(cfg):
    first, count = cfg["experts_held"]
    offset = cfg["layer_offset"]
    held = range(offset, offset + cfg["num_hidden_layers"])
    return dict(
        d=cfg["hidden_size"], hd=cfg["head_dim"], hkv=cfg["num_key_value_heads"],
        v=cfg["input"]["vocab"], e=cfg["router_experts"], first=first,
        held=count, k=cfg["num_experts_per_tok"], m=cfg["moe_intermediate_size"],
        ms=cfg["shared_expert_intermediate_size"], dense=cfg["intermediate_size"],
        window=cfg["sliding_window"], layers=list(held),
        kinds=[cfg["layer_types"][l] for l in held],
        heads=[cfg["num_attention_heads_per_layer"][l] for l in held],
        mlp_only=[l in cfg["mlp_only_layers"] for l in held])


def param_shapes(cfg):
    """``{path: (shape, kind)}``: ``kind`` a fan-in (normal, deviation
    ``fan ** -0.5``), ``"unit"`` (normal, deviation one) or ``"one"``."""
    z = _sizes(cfg)
    d, hd, hkv = z["d"], z["hd"], z["hkv"]
    s = {("embed", "embedding"): ((z["v"], d), "unit"),
         ("final_norm", "scale"): ((d,), "one"),
         ("head", "kernel"): ((d, z["v"]), d)}
    for i, (h, dense) in enumerate(zip(z["heads"], z["mlp_only"])):
        layer = {("attn_norm", "scale"): ((d,), "one"),
                 ("mlp_norm", "scale"): ((d,), "one"),
                 ("attn", "q", "kernel"): ((d, h, hd), d),
                 ("attn", "k", "kernel"): ((d, hkv, hd), d),
                 ("attn", "v", "kernel"): ((d, hkv, hd), d),
                 ("attn", "gate", "kernel"): ((d, h), d),
                 ("attn", "out", "kernel"): ((h, hd, d), h * hd)}
        ffs = [("mlp", z["dense"])] if dense else [("shared", z["ms"])]
        for name, width in ffs:
            layer[(name, "gate", "kernel")] = ((d, width), d)
            layer[(name, "up", "kernel")] = ((d, width), d)
            layer[(name, "down", "kernel")] = ((width, d), width)
        if not dense:
            m = z["m"]
            layer[("moe", "router")] = ((d, z["e"]), d)
            layer[("moe", "w_gate")] = ((z["held"], d, m), d)
            layer[("moe", "w_up")] = ((z["held"], d, m), d)
            layer[("moe", "w_down")] = ((z["held"], m, d), m)
        s.update({(f"layer{i}",) + path: v for path, v in layer.items()})
    return s


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
            scale = 1.0 if kind == "unit" else kind ** -0.5
            flat[path] = scale * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    z = _sizes(cfg)
    state = {(f"layer{i}", "moe", leaf): jnp.zeros((z["e"],), jnp.float32)
             for i, dense in enumerate(z["mlp_only"]) if not dense
             for leaf in ("bias", "load")}
    return _nest(flat), ({"router": _nest(state)} if state else {})


# -- the layers ----------------------------------------------------------------

def _rms(cfg, prec, x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return prec.store(x * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * scale)


def rotary(cfg, kind: str):
    """``(features that turn, their inverse frequencies, the factor on
    cos and sin)`` of a layer of ``kind``, by ``rope_parameters``."""
    rp = cfg["rope_parameters"][kind]
    dim = int(cfg["head_dim"] * rp["partial_rotary_factor"])
    base = float(rp["rope_theta"])
    own = base ** -(jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rp["rope_type"] != "yarn":
        return dim, own, 1.0

    def pair_of(turns):
        return (dim * math.log(rp["original_max_position_embeddings"]
                               / (turns * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(pair_of(rp["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    inv = own / rp["factor"] * ramp + own * (1.0 - ramp)
    return dim, inv, float(rp["attention_factor"])


def _rope(x, dim, inv, scale):
    """``x`` [T, heads, features]: features ``2i``, ``2i + 1`` of the
    first ``dim`` turned by position times ``inv[i]``, with ``cos`` and
    ``sin`` times ``scale``; the rest unchanged."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = (scale * jnp.cos(ang)[:, None, :], scale * jnp.sin(ang)[:, None, :])
    x1, x2 = x[..., 0:dim:2], x[..., 1:dim:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       axis=-1).reshape(*x.shape[:-1], dim)
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


def attention(cfg, prec, p, x, index: int):
    """The attention of published layer ``index`` on one row ``x`` [T,
    hidden]."""
    kind = cfg["layer_types"][index]
    h, hkv, hd = (cfg["num_attention_heads_per_layer"][index],
                  cfg["num_key_value_heads"], cfg["head_dim"])
    t, group = x.shape[0], h // hkv
    q = prec.store(prec.einsum("td,dhf->thf", x, p["q"]["kernel"]))
    k = prec.store(prec.einsum("td,dhf->thf", x, p["k"]["kernel"]))
    v = prec.store(prec.einsum("td,dhf->thf", x, p["v"]["kernel"]))
    turn = rotary(cfg, kind)
    q = prec.store(_rope(q, *turn)) / math.sqrt(hd)
    k = prec.store(_rope(k, *turn))
    q = q.reshape(t, hkv, group, hd)   # query head i reads key head i // group
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    key_pos = jnp.arange(t)

    def rows(args):
        q_blk, start = args
        q_pos = (start + jnp.arange(q_blk.shape[0]))[:, None]
        seen = key_pos <= q_pos
        if window is not None:
            seen &= key_pos > q_pos - window
        s = prec.einsum("qhgf,khf->hgqk", q_blk, k)
        s = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return prec.einsum("hgqk,khf->qhgf", s, v)

    blk = Q_BLOCK if t % Q_BLOCK == 0 else t
    o = jax.lax.map(jax.checkpoint(rows), (
        q.reshape(t // blk, blk, hkv, group, hd), jnp.arange(t // blk) * blk))
    o = prec.store(o.reshape(t, h, hd))
    gate = jax.nn.sigmoid(prec.store(prec.einsum("td,dh->th", x, p["gate"]["kernel"])))
    o = prec.store(o * gate[..., None])
    return prec.store(prec.einsum("thf,hfd->td", o, p["out"]["kernel"]))


def _swiglu(prec, x, p):
    g = prec.store(prec.einsum("...d,dm->...m", x, p["gate"]["kernel"]))
    u = prec.store(prec.einsum("...d,dm->...m", x, p["up"]["kernel"]))
    return prec.store(prec.einsum("...m,md->...d", prec.store(jax.nn.silu(g) * u),
                                  p["down"]["kernel"]))


def route(cfg, x, router, bias):
    """``(chosen [.., k], weights [.., k], load [experts])``: float32
    throughout, whatever the precision of the rest."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), router, precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["moe_routed_scaling_factor"]
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
        out = _swiglu(prec, x, {"gate": {"kernel": gate}, "up": {"kernel": up},
                                "down": {"kernel": down}})
        return total + out * weight[..., None], None

    total, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32), (
        first + jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]))
    return prec.store(total)


def expert_mlp(cfg, prec, p, state, x):
    """The routed experts held here and the shared expert on tokens ``x``
    [N, hidden]; the router's state with this step's load."""
    chosen, w, load = route(cfg, x, p["moe"]["router"], state["moe"]["bias"])
    y = routed_part(cfg, prec, p["moe"], x, chosen, w) + _swiglu(prec, x, p["shared"])
    return prec.store(y), {"moe": {"bias": state["moe"]["bias"], "load": load}}


def layer(cfg, prec, p, state, x, *, index: int):
    """Published layer ``index`` on the block's rows ``x`` [rows, T,
    hidden]; ``state`` is its router's, None for a dense layer.  The
    attention runs a row at a time, the FF over all the rows' tokens."""
    def operator(row):
        return attention(cfg, prec, p["attn"],
                         _rms(cfg, prec, row, p["attn_norm"]["scale"]), index)

    x = prec.store(x + jax.lax.map(jax.checkpoint(operator), x))
    y = _rms(cfg, prec, x, p["mlp_norm"]["scale"]).reshape(-1, x.shape[-1])
    if state is None:
        out, new = _swiglu(prec, y, p["mlp"]), None
    else:
        out, new = expert_mlp(cfg, prec, p, state, y)
    return prec.store(x + out.reshape(x.shape)), new


def loss_sum(cfg, prec, params, x, tokens):
    """The sum over rows of each row's mean next-token loss, ``x`` [rows,
    T, hidden] the last layer's output: the head over blocks of all the
    rows' positions."""
    t = tokens.shape[1]
    target = jnp.concatenate(
        [tokens[:, 1:], jnp.full((tokens.shape[0], 1), -1, tokens.dtype)], axis=1)

    def block(args):
        xb, tb = args
        logits = prec.einsum("td,dv->tv",
                             _rms(cfg, prec, xb, params["final_norm"]["scale"]),
                             params["head"]["kernel"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(tb >= 0, nll, 0.0))

    n = x.shape[0] * t
    blk = FF_BLOCK if n % FF_BLOCK == 0 else n
    sums = jax.lax.map(jax.checkpoint(block), (
        x.reshape(n // blk, blk, -1), target.reshape(n // blk, blk)))
    return jnp.sum(sums) / (t - 1)


def row_loss_sum(cfg, prec, params, model_state, tokens):
    """The sum over the block's rows of each row's loss, and the
    routers' state with the block's load.  For memory only, each layer
    is rematerialised."""
    z = _sizes(cfg)
    routers = model_state.get("router", {})
    x = prec.store(params["embed"]["embedding"][tokens])
    loads = {}
    for i, l in enumerate(z["layers"]):
        name = f"layer{i}"
        state = routers.get(name)
        x, new = jax.checkpoint(functools.partial(layer, cfg, prec, index=l))(
            params[name], state, x)
        if new is not None:
            loads[(name, "moe", "bias")] = state["moe"]["bias"]
            loads[(name, "moe", "load")] = new["moe"]["load"]
    return (loss_sum(cfg, prec, params, x, tokens),
            {"router": _nest(loads)} if loads else {})


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


# One block a step: the harness would keep a block's gradient while it
# computes the next, and five float32 copies of 654M parameters (the
# weights, the first weights, two moments, the gradient) are 13.1 GB.
ROW_BLOCK = None


def attended_pairs(t: int, window=None) -> int:
    """Query-key pairs one row and head attends: the causal square, or
    a band of the ``window`` newest keys."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass: per layer the
    four projections and the gate, each query head's two products (scores
    and values) at ``head_dim`` over the pairs it attends; the dense
    SwiGLU, or the router, the shared expert and the routed experts held
    here at the share of a token's ``num_experts_per_tok`` expected to
    fall on them; the head.  The embedding is a gather."""
    z = _sizes(cfg)
    t, d, hd, hkv = cfg["input"]["seq_len"], z["d"], z["hd"], z["hkv"]
    total = t * d * z["v"]
    for kind, h, dense in zip(z["kinds"], z["heads"], z["mlp_only"]):
        window = z["window"] if kind == "sliding_attention" else None
        total += t * (2 * d * h * hd + 2 * d * hkv * hd + d * h)
        total += attended_pairs(t, window) * h * 2 * hd
        if dense:
            total += t * 3 * d * z["dense"]
        else:
            total += t * (d * z["e"] + 3 * d * z["ms"]
                          + z["k"] * z["held"] / z["e"] * 3 * d * z["m"])
    return int(total)


# the layers the loss reads: the untied head and the norm before it
HEAD = ("head", "final_norm")
