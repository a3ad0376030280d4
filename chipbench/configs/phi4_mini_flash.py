"""Plain reference for the ``phi4_mini_flash`` configuration:
Phi-4-mini-flash-reasoning (``model_type: phi4flash``, the SambaY
decoder-hybrid-decoder of arXiv:2507.06607) under its next-token loss, as
far as its public ``config.json`` and the published modeling file state
it.  Straightforward ``jax.numpy``, float32; nothing here imports the
program, and the parameter tree only carries the names the program's
tree has.

Each layer, ``x`` of ``[rows, T, hidden_size]``: ``h = x + Op(LN1(x))``,
``y = h + MLP(LN2(h))``, both sums in float32; ``LN`` with scale and
bias, eps ``layer_norm_eps``; ``MLP(x) = (silu(x W_g) * x W_u) W_d``
with ``[W_g | W_u]`` one ``fc1``.  After the last layer one more ``LN``,
then logits ``LN(x) E^T`` with ``E`` the embedding (tied).  The loss is
the mean over positions of ``-log softmax(logits[t])[x_{t+1}]``.

``Op`` by published index ``l`` (``layer_offset`` plus the place in the
stack), ``half`` = published layers / 2, ``mb_per_layer`` = 2:

* Mamba-1 (``l`` even, ``l <= half``): ``[u | z] = x W_in``;
  ``u' = silu(conv_4(u) + b)``, a causal depthwise filter of 4 taps;
  ``[dt | B | C] = u' W_x``; ``delta = softplus(dt W_dt + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(delta_t A) s_{t-1} + delta_t u'_t B_t``,
  ``y_t = s_t C_t + D u'_t`` per channel, a ``lax.scan`` over time;
  ``(y * silu(z)) W_out``.  At ``l == half``, ``y`` is the memory ``m``.
* differential attention (``l`` odd, ``l <= half + 1``): ``q, k, v = x
  W_qkv``; head ``i`` (of ``heads / 2``) has queries ``q[2i]``,
  ``q[2i+1]``, reads key pair ``j = i // 2`` (``k[2j]``, ``k[2j+1]``)
  and the value ``[v[2j] | v[2j+1]]``; ``A^a = softmax(q^a k^a^T /
  sqrt(head) + mask)``, a causal band of the ``sliding_window`` newest
  keys (the query's own among them) for ``l < half`` and fully causal at
  ``l == half + 1``, whose ``k``, ``v`` are kept; ``lambda = exp(lq1 .
  lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6
  exp(-0.3 l)``; ``o_i = RMSNorm((A^1 - lambda A^2) V) * (1 -
  lambda_init)``; ``concat(o) W_o``.
* gated memory unit (``l`` even, ``l >= half + 2``): ``(m * silu(x W_1))
  W_2``.
* cross attention (``l`` odd, ``l >= half + 3``): ``q = x W_q`` over the
  kept ``k``, ``v``, the same differential formula, causal.

Departures and silences, each also under ``assumed`` in the ``.json``:
Mamba's sizes (``d_state`` 16, ``d_conv`` 4, ``expand`` 2, ``dt_rank``
160) are the published code's, not the config's; the memory is ``y``
before the ``z`` gate; the window's 512 keys include the query's own;
no rotary positions (the config names none); the optimizer is the other
decoder cells'.

The chip's share: ``num_hidden_layers`` of them from published layer
``layer_offset``, and the embedding (so also the head) over the
``input.vocab`` ids of the slice.

For memory only: each layer is rematerialised (``jax.checkpoint``); its
operator runs the rows one after another (``lax.map``), the MLP and the
head run over blocks of all the rows' positions, so no loop nests in
another and sums a weight's gradient twice over; inside a row the scan's
gradient goes through checkpoints of ``SCAN_CHUNK`` positions and
attention runs over blocks of query rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

SCAN_CHUNK = 64   # positions between the scan's checkpoints; memory only
Q_BLOCK = 256     # query rows an attention block; memory only
FF_BLOCK = 1024   # positions an MLP or head block; memory only


def _sizes(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    mb = cfg["mamba"]
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"], hd=d // h,
                m=cfg["intermediate_size"], v=cfg["input"]["vocab"],
                layers=cfg["num_hidden_layers"], first=cfg["layer_offset"],
                half=cfg["published"]["num_hidden_layers"] // 2,
                mb=cfg["mb_per_layer"], window=cfg["sliding_window"],
                eps=cfg["layer_norm_eps"], di=mb["expand"] * d,
                n=mb["d_state"], taps=mb["d_conv"], r=mb["dt_rank"])


def kind_of(cfg, index: int) -> str:
    """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``: the
    operator of published layer ``index``."""
    z = _sizes(cfg)
    if index % z["mb"] == 0:
        return "mamba" if index <= z["half"] else "gmu"
    if index < z["half"]:
        return "window"
    return "full" if index == z["half"] + 1 else "cross"


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def param_shapes(cfg):
    """``{path: (shape, kind)}``: ``kind`` a fan-in (normal, deviation
    ``fan ** -0.5``), ``"one"``, ``"zero"``, ``"lambda"`` (normal,
    deviation 0.1), ``"A_log"`` or ``"dt_bias"``."""
    z = _sizes(cfg)
    d, di, hd, m = z["d"], z["di"], z["hd"], z["m"]
    ln = lambda name: {(name, "scale"): ((d,), "one"),  # noqa: E731
                       (name, "bias"): ((d,), "zero")}
    s = {("embed", "embedding"): ((z["v"], d), d), **ln("final_norm")}
    for i in range(z["layers"]):
        index = z["first"] + i
        kind = kind_of(cfg, index)
        layer = {**ln("ln1"), **ln("ln2"),
                 ("mlp", "fc1", "kernel"): ((d, 2 * m), d),
                 ("mlp", "fc2", "kernel"): ((m, d), m)}
        if kind == "mamba":
            layer.update({
                ("mamba", "in_proj", "kernel"): ((d, 2 * di), d),
                ("mamba", "conv_weight"): ((di, z["taps"]), z["taps"]),
                ("mamba", "conv_bias"): ((di,), "zero"),
                ("mamba", "x_proj", "kernel"): ((di, z["r"] + 2 * z["n"]), di),
                ("mamba", "dt_proj", "kernel"): ((z["r"], di), z["r"]),
                ("mamba", "dt_proj", "bias"): ((di,), "dt_bias"),
                ("mamba", "A_log"): ((di, z["n"]), "A_log"),
                ("mamba", "D"): ((di,), "one"),
                ("mamba", "out_proj", "kernel"): ((di, d), di)})
        elif kind == "gmu":
            layer.update({("gmu", "in_proj", "kernel"): ((d, di), d),
                          ("gmu", "out_proj", "kernel"): ((di, d), di)})
        else:
            if kind == "cross":
                layer[("attn", "Wq", "kernel")] = ((d, z["h"] * hd), d)
            else:
                layer[("attn", "Wqkv", "kernel")] = (
                    (d, (z["h"] + 2 * z["hkv"]) * hd), d)
            layer[("attn", "out_proj", "kernel")] = ((z["h"] * hd, d), z["h"] * hd)
            layer[("attn", "subln")] = ((2 * hd,), "one")
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                layer[("attn", name)] = ((hd,), "lambda")
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
    """Seeded weights, Mamba's published initialisation where it has
    one: ``A_log = log(1..N)`` for every channel, ``D = 1``, ``b_dt =
    softplus^-1`` of a step log-uniform in [0.001, 0.1]; every other
    matrix (and the filter) normal with deviation 1/sqrt(fan-in), the
    ``lambda`` vectors normal with deviation 0.1, norms' scales one and
    biases nought.  No model state."""
    flat = {}
    for i, (path, (shape, kind)) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "one":
            flat[path] = jnp.ones(shape, jnp.float32)
        elif kind == "zero":
            flat[path] = jnp.zeros(shape, jnp.float32)
        elif kind == "A_log":
            flat[path] = jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
        elif kind == "dt_bias":
            lo, hi = math.log(0.001), math.log(0.1)
            dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, shape, jnp.float32))
            flat[path] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            x = jax.random.normal(k, shape, jnp.float32)
            flat[path] = (0.1 if kind == "lambda" else kind ** -0.5) * x
    return _nest(flat), {}


# -- the layers, on one row ([T, ...]) ----------------------------------------

def _ln(cfg, prec, x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return prec.store((x - mean) * jax.lax.rsqrt(var + cfg["layer_norm_eps"])
                      * p["scale"] + p["bias"])


def _by_blocks(fn, x, block):
    """``fn`` over blocks of ``block`` positions of ``x`` [T, ...], one
    after another, each rematerialised."""
    t = x.shape[0]
    blk = block if t % block == 0 else t
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(t // blk, blk, *x.shape[1:]))
    return out.reshape(t, *out.shape[2:])


def scan(u, delta, A, B, C, D):
    """The selective scan over one row: ``u``, ``delta`` [T, C], ``A``
    [C, N], ``B``, ``C`` [T, N] -> ``y`` [T, C]; a ``lax.scan`` over
    time, its gradient through checkpoints every ``SCAN_CHUNK``
    positions."""
    t = u.shape[0]
    k = SCAN_CHUNK if t % SCAN_CHUNK == 0 else t

    def step(s, xs):
        u_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * A) * s + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1) + D * u_t

    @jax.checkpoint
    def chunk(s, xs):
        return jax.lax.scan(step, s, xs)

    xs = tuple(x.reshape(t // k, k, x.shape[-1]) for x in (u, delta, B, C))
    _, y = jax.lax.scan(chunk, jnp.zeros(A.shape, jnp.float32), xs)
    return y.reshape(t, -1)


def mamba(cfg, prec, p, x):
    """``(out, y)`` of the Mamba layer on one row ``x`` [T, hidden]."""
    z = _sizes(cfg)
    di, n, r, taps = z["di"], z["n"], z["r"], z["taps"]
    uz = prec.store(prec.einsum("td,de->te", x, p["in_proj"]["kernel"]))
    u = jnp.pad(uz[:, :di], ((taps - 1, 0), (0, 0)))
    t = x.shape[0]
    conv = sum(u[j:j + t] * p["conv_weight"][:, j] for j in range(taps))
    u = jax.nn.silu(conv + p["conv_bias"])
    dbc = prec.store(prec.einsum("tc,ce->te", u, p["x_proj"]["kernel"]))
    delta = jax.nn.softplus(prec.einsum("tr,rc->tc", dbc[:, :r],
                                        p["dt_proj"]["kernel"])
                            + p["dt_proj"]["bias"])
    y = scan(u, delta, -jnp.exp(p["A_log"]), dbc[:, r:r + n], dbc[:, r + n:],
             p["D"])
    gated = y * jax.nn.silu(uz[:, di:])
    return prec.store(prec.einsum("tc,cd->td", gated, p["out_proj"]["kernel"])), y


def diff_attention(cfg, prec, p, x, kv, index, kind):
    """``(out, (k, v))`` of differential attention on one row ``x`` [T,
    hidden]: over its own keys, or (``cross``) over ``kv``."""
    z = _sizes(cfg)
    h, hkv, hd, t = z["h"], z["hkv"], z["hd"], x.shape[0]
    if kind == "cross":
        q = prec.einsum("td,de->te", x, p["Wq"]["kernel"])
        k, v = kv
    else:
        qkv = prec.einsum("td,de->te", x, p["Wqkv"]["kernel"])
        q = qkv[:, :h * hd]
        k = prec.store(qkv[:, h * hd:(h + hkv) * hd].reshape(t, hkv, hd))
        v = prec.store(qkv[:, (h + hkv) * hd:].reshape(t, hkv, hd))
    q = prec.store(q.reshape(t, h, hd))
    group = h // hkv
    # head i's two queries, and the key pair and value its j = i // 2 names
    pairs = [(q[:, a::2], jnp.repeat(k[:, a::2], group, axis=1)) for a in (0, 1)]
    vals = jnp.repeat(v.reshape(t, hkv // 2, 2 * hd), group, axis=1)
    init = lambda_init(index)
    lam = (jnp.exp(jnp.dot(p["lambda_q1"], p["lambda_k1"], precision="highest"))
           - jnp.exp(jnp.dot(p["lambda_q2"], p["lambda_k2"], precision="highest"))
           + init)
    window = z["window"] if kind == "window" else None
    key_pos = jnp.arange(t)

    def rows(args):
        (q1, q2), start = args
        q_pos = (start + jnp.arange(q1.shape[0]))[:, None]
        seen = key_pos <= q_pos
        if window is not None:
            seen &= key_pos > q_pos - window
        probs = [jax.nn.softmax(jnp.where(
            seen[None], prec.einsum("qhf,khf->hqk", qa, ka) / math.sqrt(hd),
            -jnp.inf), axis=-1) for qa, (_, ka) in zip((q1, q2), pairs)]
        return prec.einsum("hqk,khf->qhf", probs[0] - lam * probs[1], vals)

    blk = Q_BLOCK if t % Q_BLOCK == 0 else t
    split = lambda a: a.reshape(t // blk, blk, *a.shape[1:])  # noqa: E731
    o = jax.lax.map(jax.checkpoint(rows), ((split(pairs[0][0]), split(pairs[1][0])),
                                          jnp.arange(t // blk) * blk))
    o = o.reshape(t, h // 2, 2 * hd)
    o = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                           + cfg["layer_norm_eps"]) * p["subln"] * (1.0 - init))
    out = prec.einsum("te,ed->td", prec.store(o).reshape(t, h * hd),
                      p["out_proj"]["kernel"])
    return prec.store(out), (k, v)


def gmu(prec, p, x, memory):
    gate = prec.einsum("td,dc->tc", x, p["in_proj"]["kernel"])
    return prec.store(prec.einsum(
        "tc,cd->td", memory * jax.nn.silu(gate), p["out_proj"]["kernel"]))


def mlp(prec, p, x):
    gu = prec.einsum("td,dm->tm", x, p["fc1"]["kernel"])
    g, u = jnp.split(gu, 2, axis=-1)
    return prec.store(prec.einsum("tm,md->td", prec.store(jax.nn.silu(g) * u),
                                  p["fc2"]["kernel"]))


def layer(cfg, prec, p, x, memory, kv, *, index):
    """One layer on the block's rows ``x`` [rows, T, hidden]: ``(x,
    memory, kv)`` handed on.  The operator runs a row at a time, the MLP
    over blocks of all the rows' positions."""
    kind, half = kind_of(cfg, index), _sizes(cfg)["half"]

    def operator(args):
        x, memory, kv = args
        y = _ln(cfg, prec, x, p["ln1"])
        if kind == "mamba":
            out, scanned = mamba(cfg, prec, p["mamba"], y)
            return out, (scanned if index == half else memory), kv
        if kind == "gmu":
            return gmu(prec, p["gmu"], y, memory), memory, kv
        out, read = diff_attention(cfg, prec, p["attn"], y, kv, index, kind)
        return out, memory, (read if kind == "full" else kv)

    out, memory, kv = jax.lax.map(jax.checkpoint(operator), (x, memory, kv))
    x = x + out
    ff = _by_blocks(lambda b: mlp(prec, p["mlp"], _ln(cfg, prec, b, p["ln2"])),
                    x.reshape(-1, x.shape[-1]), FF_BLOCK)
    return x + ff.reshape(x.shape), memory, kv


def loss_sum(cfg, prec, params, x, tokens):
    """The sum over rows of each row's mean next-token loss, ``x``
    [rows, T, hidden] the last layer's output: the head over blocks of
    all the rows' positions."""
    t = tokens.shape[1]
    target = jnp.concatenate(
        [tokens[:, 1:], jnp.full((tokens.shape[0], 1), -1, tokens.dtype)], axis=1)
    emb = params["embed"]["embedding"]

    def block(args):
        xb, tb = args
        logits = prec.einsum("td,vd->tv",
                             _ln(cfg, prec, xb, params["final_norm"]), emb)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(tb >= 0, nll, 0.0))

    n = x.shape[0] * t
    blk = FF_BLOCK if n % FF_BLOCK == 0 else n
    sums = jax.lax.map(jax.checkpoint(block), (
        x.reshape(n // blk, blk, -1), target.reshape(n // blk, blk)))
    return jnp.sum(sums) / (t - 1)


def row_loss_sum(cfg, prec, params, model_state, tokens):
    """The sum over the block's rows of each row's loss.  For memory
    only, each layer is rematerialised."""
    z = _sizes(cfg)
    x = params["embed"]["embedding"][tokens]
    memory = kv = None
    for i in range(z["layers"]):
        one_layer = jax.checkpoint(functools.partial(
            layer, cfg, prec, index=z["first"] + i))
        x, memory, kv = one_layer(params[f"layer{i}"], x, memory, kv)
    return loss_sum(cfg, prec, params, x, tokens), model_state


# One block a step: the harness would keep a block's gradient while it
# computes the next, and five float32 copies of 697M parameters (the
# weights, the first weights, two moments, the gradient) are 13.9 GB.
ROW_BLOCK = None


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass: per layer the
    products of its operator (Mamba's four, attention's projections,
    the gated memory unit's two) and of the MLP; each attention head's
    two score products at ``head`` and two value products at ``2 head``
    over the pairs its queries attend; the head.  The embedding is a
    gather; the filter and the scan are elementwise."""
    z = _sizes(cfg)
    t, d, di, hd, h, w = (cfg["input"]["seq_len"], z["d"], z["di"], z["hd"],
                          z["h"], z["window"])
    band = w * (w + 1) // 2 + max(t - w, 0) * w if t > w else t * (t + 1) // 2
    causal = t * (t + 1) // 2
    heads = h // 2 * 6 * hd   # per pair a head attends
    total = t * d * z["v"]
    for i in range(z["layers"]):
        kind = kind_of(cfg, z["first"] + i)
        total += t * 3 * d * z["m"]
        if kind == "mamba":
            total += t * (2 * d * di + di * (z["r"] + 2 * z["n"]) + z["r"] * di
                          + di * d)
        elif kind == "gmu":
            total += t * 2 * d * di
        elif kind == "cross":
            total += t * 2 * d * h * hd + causal * heads
        else:
            total += (t * (d * (h + 2 * z["hkv"]) * hd + h * hd * d)
                      + (band if kind == "window" else causal) * heads)
    return int(total)


# the layers the loss reads: the tied embedding and the norm before it
HEAD = ("embed", "final_norm")
