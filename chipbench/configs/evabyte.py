"""Plain reference for the ``evabyte`` configuration: EvaByte
(``model_type: evabyte``) under its training loss, as far as its public
``config.json`` and the published estimator (Zheng, Yuan, Wang, Kong,
*Efficient Attention via Control Variates*, arXiv:2302.04542) state it.
Straightforward ``jax.numpy``, float32; nothing here imports the
program, and the parameter tree only carries the names the program's
tree has.

One layer, ``x`` of ``[rows, T, hidden_size]`` with ``T`` a multiple of
``window_size``; ``rms(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(``norm_add_unit_offset``): ``h = x + Attn(rms_1(x))``; ``y = h +
FF(rms_2(h))``, both sums in float32 (``fp32_skip_add``: no mode of this
file rounds the residual stream); ``FF(x) = W_down(silu(W_gate x) *
W_up x)``.  After the last layer one more ``rms``, then the head
``W_head`` of ``[hidden_size, num_pred_heads x vocab]``: output ``i`` at
position ``t`` scores byte ``t + 1 + i``.

EVA attention, per head ``a`` with two learned vectors ``mu_a``,
``phi_a``: ``q, k, v = x W_q, x W_k, x W_v``, no bias; rotary positions
on all features of ``q`` and ``k`` by absolute position.  The row is cut
into windows of ``window_size`` and chunks of ``chunk_size``.  For chunk
``c``: ``k~_c = sum_m softmax_m(mu_a . k_m) k_m`` and ``v~_c = sum_m
softmax_m(phi_a . k_m) v_m``, both softmaxes over the chunk's positions,
in float32.  A query at ``t`` in window ``w = t // window_size`` attends
the keys ``m <= t`` of window ``w`` and the summaries of every chunk of
the windows before ``w`` (none of its own), under ONE softmax of the
scores ``q . k / sqrt(head)`` over both; then ``W_o``.  Here that is
computed the plain way: for a block of queries, one softmax over the
concatenation of the row's keys and all its summaries under one mask.

The loss is ``sum_i CE_i`` over the ``num_pred_heads`` outputs, ``CE_i``
the mean over the positions ``t`` with ``t + 1 + i < T`` of ``-log
softmax(logits_i[t])[x_{t+1+i}]``, logits in float32.

Departures and silences, each also under ``assumed`` in the ``.json``:
the pooling logits are ``mu . k`` and ``phi . k`` with no further scale
and no ``-|k|^2 / 2`` term (the paper's proposal has one; the family's
implementation learns ``mu``, ``phi`` as free per-head vectors), ``k``
pooled after its rotary positions; the heads' equal weights in the loss;
rotary on neighbouring pairs where the published code rotates halves
(seeded weights cannot tell them apart); ``mixedp_attn`` read as: the
softmaxes in float32; the optimizer is the GLM cell's.

The chip's share: ``heads_held = [first, count]`` of the layer's
``layer_heads`` (the published ``num_attention_heads``, which fixes the
head's size) live here, with their columns of ``W_q``,
``W_k``, ``W_v``, their ``mu``, ``phi`` and their rows of ``W_o``: the
attention's result is this chip's part of the layer's sum, and what the
absent heads would add is left out.  With ``[0, layer_heads]`` this file is the
uncut layer.

For memory only: a row and, within it, each layer are rematerialised
(``jax.checkpoint``), attention runs over blocks of query rows, the
feed-forward over blocks of positions, and the rows of a block run one
after another.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512    # query rows an attention block; memory only
FF_BLOCK = 2048  # positions a feed-forward block; memory only


def _sizes(cfg):
    d, h = cfg["hidden_size"], cfg["layer_heads"]
    first, held = cfg["heads_held"]
    return dict(d=d, h=h, hd=d // h, first=first, held=held,
                m=cfg["intermediate_size"], v=cfg["input"]["vocab"],
                layers=cfg["num_hidden_layers"], heads=cfg["num_pred_heads"],
                window=cfg["window_size"], chunk=cfg["chunk_size"])


def param_shapes(cfg):
    """``{path: (shape, kind)}``: ``kind`` a fan-in (normal, deviation
    ``fan ** -0.5``), ``"zero"`` (a norm's ``w``) or ``"summary"``."""
    z = _sizes(cfg)
    d, held, hd, m = z["d"], z["held"], z["hd"], z["m"]
    s = {("embed", "embedding"): ((z["v"], d), 1),
         ("final_norm", "scale"): ((d,), "zero"),
         ("lm_head", "kernel"): ((d, z["heads"] * z["v"]), d)}
    for i in range(z["layers"]):
        s.update({(f"layer{i}",) + path: v for path, v in {
            ("attn_norm", "scale"): ((d,), "zero"),
            ("ffn_norm", "scale"): ((d,), "zero"),
            ("attn", "q", "kernel"): ((d, held, hd), d),
            ("attn", "k", "kernel"): ((d, held, hd), d),
            ("attn", "v", "kernel"): ((d, held, hd), d),
            ("attn", "mu"): ((held, hd), "summary"),
            ("attn", "phi"): ((held, hd), "summary"),
            # the fan-in of the uncut layer's W_o, whose rows these are
            ("attn", "out", "kernel"): ((held, hd, d), z["h"] * hd),
            ("mlp", "gate", "kernel"): ((d, m), d),
            ("mlp", "up", "kernel"): ((d, m), d),
            ("mlp", "down", "kernel"): ((m, d), m),
        }.items()})
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
    """Seeded weights: every kernel normal with deviation
    1/sqrt(fan-in), the embedding with deviation 1, the norms' ``w``
    nought, ``mu`` and ``phi`` normal with deviation ``head ** -0.5``
    clipped to one deviation.  No model state."""
    flat = {}
    for i, (path, (shape, kind)) in enumerate(sorted(param_shapes(cfg).items())):
        if kind == "zero":
            flat[path] = jnp.zeros(shape, jnp.float32)
            continue
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        flat[path] = (shape[-1] ** -0.5 * jnp.clip(x, -1.0, 1.0)
                      if kind == "summary" else kind ** -0.5 * x)
    return _nest(flat), {}


# -- the layer ---------------------------------------------------------------

def _rms(cfg, prec, x, w):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return prec.store(x * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * (1.0 + w))


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


def summaries(cfg, prec, k, v, mu, phi):
    """Each chunk's pooled key and value, ``[rows, T / chunk, heads,
    features]``: float32 whatever the precision of the products."""
    c = cfg["chunk_size"]
    b, t, h, f = k.shape
    kc, vc = (x.reshape(b, t // c, c, h, f) for x in (k, v))
    pool = lambda w, x: jnp.einsum(  # noqa: E731
        "bcmh,bcmhf->bchf", jax.nn.softmax(jnp.einsum(
            "bcmhf,hf->bcmh", kc, w, precision=HIGHEST), axis=2), x,
        precision=HIGHEST)
    return prec.store(pool(mu, kc)), prec.store(pool(phi, vc))


def attention(cfg, prec, p, x):
    """EVA attention over the heads held here, ``x`` [rows, T, hidden]."""
    z = _sizes(cfg)
    t, window, chunk = x.shape[1], z["window"], z["chunk"]
    if t % window or window % chunk:
        raise ValueError(f"a row of {t} is no multiple of the window "
                         f"({window}), or the window of the chunk ({chunk})")
    q = prec.store(prec.einsum("btd,dhf->bthf", x, p["q"]["kernel"]))
    k = prec.store(prec.einsum("btd,dhf->bthf", x, p["k"]["kernel"]))
    v = prec.store(prec.einsum("btd,dhf->bthf", x, p["v"]["kernel"]))
    q, k = prec.store(_rope(cfg, q)), prec.store(_rope(cfg, k))
    q = prec.store(q / jnp.sqrt(jnp.float32(z["hd"])))
    ksum, vsum = summaries(cfg, prec, k, v, p["mu"], p["phi"])
    keys = jnp.concatenate([k, ksum], axis=1)
    vals = jnp.concatenate([v, vsum], axis=1)
    # a key's window; its position, or for a summary none
    pos = jnp.arange(t)
    first = jnp.arange(t // chunk) * chunk
    key_window = jnp.concatenate([pos // window, first // window])
    key_pos = jnp.concatenate([pos, jnp.full_like(first, -1)])

    @jax.checkpoint
    def rows(args):
        q_blk, start = args
        q_pos = (start + jnp.arange(q_blk.shape[1]))[:, None]
        exact = (key_window == q_pos // window) & (key_pos <= q_pos)
        earlier = key_window < q_pos // window
        seen = jnp.where(key_pos >= 0, exact, earlier)
        s = prec.einsum("bqhf,bkhf->bhqk", q_blk, keys)
        s = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return prec.einsum("bhqk,bkhf->bqhf", s, vals)

    blk = min(Q_BLOCK, t)
    if t % blk:
        blk = t
    n = t // blk
    q_blocks = q.reshape(q.shape[0], n, blk, *q.shape[2:]).transpose(
        1, 0, 2, 3, 4)
    out = jax.lax.map(rows, (q_blocks, jnp.arange(n) * blk))
    out = prec.store(out.transpose(1, 0, 2, 3, 4).reshape(q.shape))
    return prec.store(prec.einsum("bthf,hfd->btd", out, p["out"]["kernel"]))


def _swiglu(prec, x, gate, up, down):
    g = prec.store(prec.einsum("...d,dm->...m", x, gate))
    u = prec.store(prec.einsum("...d,dm->...m", x, up))
    return prec.store(prec.einsum(
        "...m,md->...d", prec.store(jax.nn.silu(g) * u), down))


def feed_forward(prec, p, x):
    """The dense SwiGLU on ``x`` [rows, T, hidden], over blocks of
    positions, one after another."""
    t = x.shape[1]
    blk = FF_BLOCK if t % FF_BLOCK == 0 else t
    one = jax.checkpoint(lambda y: _swiglu(
        prec, y, p["gate"]["kernel"], p["up"]["kernel"], p["down"]["kernel"]))
    return jnp.concatenate(
        [one(x[:, lo:lo + blk]) for lo in range(0, t, blk)], axis=1)


def layer(cfg, prec, p, x):
    """One layer: the residual sums are float32 in every mode."""
    x = x + attention(cfg, prec, p["attn"],
                      _rms(cfg, prec, x, p["attn_norm"]["scale"]))
    return x + feed_forward(prec, p["mlp"],
                            _rms(cfg, prec, x, p["ffn_norm"]["scale"]))


def heads_loss(cfg, logits, tokens):
    """``sum_i CE_i`` per row: ``logits`` [rows, T, heads, vocab]
    float32, ``tokens`` [rows, T]; output ``i`` at ``t`` scores token
    ``t + 1 + i``, over the positions that have one."""
    t = tokens.shape[1]
    total = 0.0
    for i in range(cfg["num_pred_heads"]):
        logp = jax.nn.log_softmax(logits[:, :t - 1 - i, i], axis=-1)
        nll = -jnp.take_along_axis(
            logp, tokens[:, 1 + i:, None], axis=-1)[..., 0]
        total = total + jnp.mean(nll, axis=-1)
    return total


def _one_row(cfg, prec, params, tokens):
    x = params["embed"]["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, x: layer(cfg, prec, p, x))(
            params[f"layer{i}"], x)
    x = _rms(cfg, prec, x, params["final_norm"]["scale"])
    logits = prec.einsum("btd,dv->btv", x, params["lm_head"]["kernel"])
    logits = logits.reshape(*logits.shape[:2], cfg["num_pred_heads"], -1)
    return jnp.sum(heads_loss(cfg, logits, tokens))


def row_loss_sum(cfg, prec, params, model_state, tokens):
    """The sum over the block's rows of each row's loss.  For memory
    only, the rows of a block run one after another (``jax.lax.map``
    over rematerialised rows)."""
    totals = jax.lax.map(
        jax.checkpoint(lambda row: _one_row(cfg, prec, params, row[None])),
        tokens)
    return jnp.sum(totals), model_state


# One block a step, as the other language configurations and for their
# reason: the harness keeps a block's gradient while it computes the
# next.  One block holds five float32 copies of 620M parameters
# (12.4 GB), the gradient (2.5 GB) and one row's work.
ROW_BLOCK = 2


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass: per layer
    the attention's four projections over the heads held, the two
    products over the pairs a query attends (its window's keys up to
    itself, and a summary for every chunk of the windows before), the
    two poolings, and the SwiGLU; the head.  The embedding is a gather."""
    z = _sizes(cfg)
    t, d, hd, held = cfg["input"]["seq_len"], z["d"], z["hd"], z["held"]
    nw, per_window = t // z["window"], z["window"] // z["chunk"]
    pairs = (nw * (z["window"] * (z["window"] + 1) // 2)
             + per_window * z["window"] * (nw * (nw - 1) // 2))
    attn = (t * 4 * d * held * hd + pairs * held * 2 * hd
            + t * held * 4 * hd)
    return int(z["layers"] * (attn + t * 3 * d * z["m"])
               + t * d * z["heads"] * z["v"])


# the layers the loss reads: the untied head and the norm before it
HEAD = ("lm_head", "final_norm")
