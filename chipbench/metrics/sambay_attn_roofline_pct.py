"""Kernels: the flash-attention kernels' share of their roofline in a
configuration with differential attention in window, full and cross
layers (the held layers' kinds as ``scan_roofline_pct`` takes them),
over the traced steps;
``attn_roofline_pct`` is its sibling for latent attention and holds how a
kernel's least time is taken from its work.

A layer makes two flash calls, one a softmax of the pair, each of
``num_heads / 2`` query heads over ``num_kv_heads / 2`` key heads of
``dim / num_heads`` features and a value of twice that width.  A query
attends a band of the ``sliding_window`` newest keys in a window layer,
every earlier key in a full or cross layer.  Over those pairs the
forward kernel makes a score product at the key width and a value
product at the value width, the dQ kernel scores, dP (value width) and
dQ, the dK/dV kernel scores, dP, dV (value width) and dK.  Bytes are
each operand and result once, in bfloat16: q, k, v, o (and dO, dQ or dK,
dV) of a call; a cross layer's k and v where a call reads them.  Each
kernel's work counts once a step, a rematerialised layer's forward too.
Nothing to read where the configuration has no such layers or the trace
holds none of the kernels."""

import os

from chipbench.harness import load_module

HERE = os.path.dirname(__file__)
MHA = load_module(os.path.join(HERE, "attn_roofline_pct.py"))
SCAN = load_module(os.path.join(HERE, "scan_roofline_pct.py"))
KERNELS = MHA.KERNELS


def step_work(config: dict, rows: int) -> dict:
    """``{kernel: (operations, bytes)}`` of one training step."""
    kinds = SCAN.held_kinds(config)
    # a cross layer attends as a full one
    layers = {"window": kinds.count("window"),
              "full": kinds.count("full") + kinds.count("cross")}
    calls = 2 * rows * (layers["window"] + layers["full"])
    if not calls:
        return {}
    kw = config["model"]["kwargs"]
    t, w = config["input"]["seq_len"], kw["sliding_window"]
    band = w * (w + 1) // 2 + (t - w) * w if t > w else t * (t + 1) // 2
    pairs = rows * 2 * (layers["window"] * band + layers["full"] * t * (t + 1) // 2)
    heads, kv, d = kw["num_heads"] // 2, kw["num_kv_heads"] // 2, kw["dim"] // kw["num_heads"]
    dv = 2 * d
    product = 2 * heads * pairs   # times the width of one product
    # a call's tensors over its T positions, bf16
    q, o = calls * t * heads * d * 2, calls * t * heads * dv * 2
    k, v = calls * t * kv * d * 2, calls * t * kv * dv * 2
    return {
        KERNELS[0]: (product * (d + dv), q + k + v + o),
        KERNELS[1]: (product * (d + dv + d), q + k + v + o + q),
        KERNELS[2]: (product * (d + dv + dv + d), q + k + v + o + k + v),
    }


def read(ctx):
    return MHA.share(ctx, step_work(
        ctx["config"], ctx["traffic"]["global_batch"] // ctx["chips"]))
