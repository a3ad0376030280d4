"""Plain reference for the ``resnet50`` configuration.

He et al., arXiv:1512.03385, Table 1, 50-layer column, in the v1.5 form
the program runs (the stride of a down-sampling block sits on its 3x3
convolution).  Straightforward ``jax.numpy``, float32, NHWC; batch
normalisation over the whole batch with the biased variance, running
statistics updated with momentum 0.9.  Nothing here imports the program;
the parameter tree only carries the names the program's tree has, so the
benchmark can hand one set of seeded weights to both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5
BN_MOMENTUM = 0.9
EXPANSION = 4


def _blocks(cfg):
    """(name, in_channels, filters, stride) of every bottleneck block."""
    out, cin, k = [], cfg["width"], 0
    for i, n in enumerate(cfg["stage_sizes"]):
        f = cfg["width"] * 2 ** i
        for j in range(n):
            out.append((f"BottleneckBlock_{k}", cin, f,
                        2 if i > 0 and j == 0 else 1))
            cin, k = f * EXPANSION, k + 1
    return out


def param_shapes(cfg):
    """name-path -> (shape, kind); kind says how the leaf is drawn."""
    w = cfg["width"]
    s = {("stem_conv", "kernel"): ((7, 7, 3, w), "conv")}

    def bn(prefix, c):
        s[prefix + ("scale",)] = ((c,), "one")
        s[prefix + ("bias",)] = ((c,), "zero")

    bn(("stem_bn",), w)
    for name, cin, f, stride in _blocks(cfg):
        s[(name, "Conv_0", "kernel")] = ((1, 1, cin, f), "conv")
        s[(name, "Conv_1", "kernel")] = ((3, 3, f, f), "conv")
        s[(name, "Conv_2", "kernel")] = ((1, 1, f, f * EXPANSION), "conv")
        bn((name, "BatchNorm_0"), f)
        bn((name, "BatchNorm_1"), f)
        bn((name, "BatchNorm_2"), f * EXPANSION)
        if stride != 1 or cin != f * EXPANSION:
            s[(name, "downsample_conv", "kernel")] = (
                (1, 1, cin, f * EXPANSION), "conv")
            bn((name, "downsample_bn"), f * EXPANSION)
    feat = cfg["width"] * 2 ** (len(cfg["stage_sizes"]) - 1) * EXPANSION
    s[("Dense_0", "kernel")] = ((feat, cfg["num_classes"]), "dense")
    s[("Dense_0", "bias")] = ((cfg["num_classes"],), "zero")
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
    """Seeded weights: He-normal convolutions (fan-in), a 1/sqrt(fan-in)
    normal classifier, every batch-norm scale 1 and bias 0 as in the
    paper (no zero-initialised last scale, so every leaf has a gradient
    from the first step).  Returns ``(params, model_state)``."""
    flat, stats = {}, {}
    for i, (path, (shape, kind)) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if kind == "conv":
            fan_in = shape[0] * shape[1] * shape[2]
            v = jax.random.normal(k, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
        elif kind == "dense":
            v = jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5
        else:
            v = jnp.full(shape, 1.0 if kind == "one" else 0.0, jnp.float32)
        flat[path] = v
        if path[-1] == "scale":
            stats[path[:-1] + ("mean",)] = jnp.zeros(shape, jnp.float32)
            stats[path[:-1] + ("var",)] = jnp.ones(shape, jnp.float32)
    return _nest(flat), {"batch_stats": _nest(stats)}


def _bn(prec, x, p, st):
    x = prec.store(x)
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {"mean": BN_MOMENTUM * st["mean"] + (1 - BN_MOMENTUM) * mean,
           "var": BN_MOMENTUM * st["var"] + (1 - BN_MOMENTUM) * var}
    return prec.store(y), new


def _block(prec, stride, p, st, x):
    new = {}
    y = prec.conv(x, p["Conv_0"]["kernel"], 1, 0)
    y, new["BatchNorm_0"] = _bn(prec, y, p["BatchNorm_0"], st["BatchNorm_0"])
    y = jax.nn.relu(y)
    y = prec.conv(y, p["Conv_1"]["kernel"], stride, 1)
    y, new["BatchNorm_1"] = _bn(prec, y, p["BatchNorm_1"], st["BatchNorm_1"])
    y = jax.nn.relu(y)
    y = prec.conv(y, p["Conv_2"]["kernel"], 1, 0)
    y, new["BatchNorm_2"] = _bn(prec, y, p["BatchNorm_2"], st["BatchNorm_2"])
    if "downsample_conv" in p:
        x = prec.conv(x, p["downsample_conv"]["kernel"], stride, 0)
        x, new["downsample_bn"] = _bn(prec, x, p["downsample_bn"],
                                      st["downsample_bn"])
    return prec.store(jax.nn.relu(y + x)), new


def forward(cfg, prec, params, model_state, images):
    """Training-mode forward: ``(logits, new_model_state)``.  Each block
    is rematerialised in the backward pass so that the float32 pass at
    the timed batch fits the chip beside nothing else."""
    st, new = model_state["batch_stats"], {}
    x = prec.conv(images, params["stem_conv"]["kernel"], 2, 3)
    x, new["stem_bn"] = _bn(prec, x, params["stem_bn"], st["stem_bn"])
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))
    for name, _, _, stride in _blocks(cfg):
        blk = jax.checkpoint(lambda p, s, h, stride=stride:
                             _block(prec, stride, p, s, h))
        x, new[name] = blk(params[name], st[name], x)
    x = prec.store(jnp.mean(x, axis=(1, 2)))
    logits = prec.einsum("bf,fc->bc", x, params["Dense_0"]["kernel"])
    return logits + params["Dense_0"]["bias"], {"batch_stats": new}


# the batch statistics span the batch: the reference takes it whole
ROW_BLOCK = None


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one image's forward pass, from the layer
    table: every convolution and the classifier, nothing else."""
    h = cfg["image"][0]
    w = cfg["width"]
    h = (h + 2 * 3 - 7) // 2 + 1
    macs = h * h * 7 * 7 * 3 * w
    h = (h + 2 - 3) // 2 + 1
    for _, cin, f, stride in _blocks(cfg):
        macs += h * h * cin * f
        h2 = (h + 2 - 3) // stride + 1
        macs += h2 * h2 * 9 * f * f + h2 * h2 * f * f * EXPANSION
        if stride != 1 or cin != f * EXPANSION:
            macs += h2 * h2 * cin * f * EXPANSION
        h = h2
    feat = cfg["width"] * 2 ** (len(cfg["stage_sizes"]) - 1) * EXPANSION
    return macs + feat * cfg["num_classes"]


# the layer the loss reads
HEAD = ("Dense_0",)
