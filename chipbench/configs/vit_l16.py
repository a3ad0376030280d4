"""Plain reference for the ``vit_l16`` configuration.

Dosovitskiy et al., arXiv:2010.11929, Table 1 (ViT-Large) with 16x16
patches: pre-norm encoder blocks, a class token read out after the final
layer norm.  Straightforward ``jax.numpy``, float32.  Departures that
follow the program and are listed under ``assumed`` in the configuration
file: the tanh approximation of GELU and a layer-norm epsilon of 1e-6.
Nothing here imports the program; the parameter tree only carries the
names the program's tree has.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _tokens(cfg) -> int:
    return (cfg["image"][0] // cfg["patch"]) ** 2 + 1


def param_shapes(cfg):
    d, h, m, p = cfg["dim"], cfg["num_heads"], cfg["mlp_dim"], cfg["patch"]
    dh = d // h
    s = {
        ("patch_embed", "kernel"): ((p, p, 3, d), p * p * 3),
        ("patch_embed", "bias"): ((d,), "zero"),
        ("cls_token",): ((1, 1, d), "small"),
        ("pos_embed",): ((1, _tokens(cfg), d), "small"),
        ("final_norm", "scale"): ((d,), "one"),
        ("final_norm", "bias"): ((d,), "zero"),
        ("head", "kernel"): ((d, cfg["num_classes"]), d),
        ("head", "bias"): ((cfg["num_classes"],), "zero"),
    }
    for i in range(cfg["depth"]):
        b = f"block{i}"
        for ln in ("LayerNorm_0", "LayerNorm_1"):
            s[(b, ln, "scale")] = ((d,), "one")
            s[(b, ln, "bias")] = ((d,), "zero")
        a = (b, "MultiHeadAttention_0")
        s[a + ("qkv", "kernel")] = ((d, 3, h, dh), d)
        s[a + ("qkv", "bias")] = ((3, h, dh), "zero")
        s[a + ("out", "kernel")] = ((h, dh, d), d)
        s[a + ("out", "bias")] = ((d,), "zero")
        s[(b, "MlpBlock_0", "Dense_0", "kernel")] = ((d, m), d)
        s[(b, "MlpBlock_0", "Dense_0", "bias")] = ((m,), "zero")
        s[(b, "MlpBlock_0", "Dense_1", "kernel")] = ((m, d), m)
        s[(b, "MlpBlock_0", "Dense_1", "bias")] = ((d,), "zero")
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
    """Seeded weights: every kernel normal with standard deviation
    1/sqrt(fan-in), class token and positions normal(0.02), norms 1 and
    0, biases 0.  Returns ``(params, model_state)``; the state is empty."""
    flat = {}
    for i, (path, (shape, kind)) in enumerate(
            sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "small":
            v = jax.random.normal(k, shape, jnp.float32) * 0.02
        elif kind in ("one", "zero"):
            v = jnp.full(shape, 1.0 if kind == "one" else 0.0, jnp.float32)
        else:
            v = jax.random.normal(k, shape, jnp.float32) * kind ** -0.5
        flat[path] = v
    return _nest(flat), {}


def _ln(prec, x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return prec.store(
        (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"])


def _block(prec, p, x):
    a = p["MultiHeadAttention_0"]
    y = _ln(prec, x, p["LayerNorm_0"])
    qkv = prec.store(
        prec.einsum("btd,dchf->btchf", y, a["qkv"]["kernel"]) + a["qkv"]["bias"])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    q = q / jnp.sqrt(jnp.float32(q.shape[-1]))
    s = jax.nn.softmax(prec.einsum("bqhf,bkhf->bhqk", q, k), axis=-1)
    y = prec.store(prec.einsum("bhqk,bkhf->bqhf", s, v))
    x = prec.store(
        x + prec.einsum("bthf,hfd->btd", y, a["out"]["kernel"]) + a["out"]["bias"])
    m = p["MlpBlock_0"]
    y = _ln(prec, x, p["LayerNorm_1"])
    y = prec.einsum("btd,dm->btm", y, m["Dense_0"]["kernel"]) + m["Dense_0"]["bias"]
    y = prec.store(jax.nn.gelu(prec.store(y), approximate=True))
    return prec.store(
        x + prec.einsum("btm,md->btd", y, m["Dense_1"]["kernel"]) + m["Dense_1"]["bias"])


def forward(cfg, prec, params, model_state, images):
    """Training-mode forward (no dropout in this configuration):
    ``(logits, model_state)``."""
    b, hh, ww, c = images.shape
    p = cfg["patch"]
    x = images.reshape(b, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, (hh // p) * (ww // p), p * p * c)
    k = params["patch_embed"]["kernel"]
    x = prec.einsum("btk,kd->btd", x, k.reshape(-1, k.shape[-1]))
    x = prec.store(x + params["patch_embed"]["bias"])
    cls = jnp.broadcast_to(params["cls_token"], (b, 1, x.shape[-1]))
    x = prec.store(jnp.concatenate([cls, x], axis=1) + params["pos_embed"])
    for i in range(cfg["depth"]):
        blk = jax.checkpoint(lambda q, h: _block(prec, q, h))
        x = blk(params[f"block{i}"], x)
    x = _ln(prec, x, params["final_norm"])[:, 0]
    logits = prec.einsum("bd,dc->bc", x, params["head"]["kernel"])
    return logits + params["head"]["bias"], model_state


# no statistic spans the batch: the reference sums gradients over blocks
# of this many rows
ROW_BLOCK = 16


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one image's forward pass: patch embedding,
    per layer the four attention projections, the two attention products
    and the two MLP products, and the head."""
    d, m, t = cfg["dim"], cfg["mlp_dim"], _tokens(cfg)
    per_layer = t * (4 * d * d + 2 * d * m) + 2 * t * t * d
    embed = (t - 1) * cfg["patch"] ** 2 * 3 * d
    return embed + cfg["depth"] * per_layer + d * cfg["num_classes"]


# the layer the loss reads
HEAD = ("head",)
