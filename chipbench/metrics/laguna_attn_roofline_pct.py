"""Kernels: the flash-attention kernels' share of their roofline in a
configuration whose layers take their attention kind and head count from
per-layer lists read by published index (``layer_types``,
``num_heads_per_layer`` from ``layer_offset``), over the traced steps;
``attn_roofline_pct`` is its sibling for latent attention and holds how a
kernel's least time is taken from its work.

A ``sliding_attention`` layer's query head attends a band of the
``sliding_window`` newest keys, a ``full_attention`` layer's every
earlier key; each query head makes its own products over its pairs at
``head_dim``: the forward kernel 2 (scores, values), the dQ kernel 3
(scores, dP, dQ), the dK/dV kernel 4 (scores, dP, dV, dK).  Bytes are
each operand and result once, in bfloat16: ``q``, ``o`` (and ``dO``,
``dQ``) at the layer's query heads, ``k``, ``v`` (and ``dK``, ``dV``) at
``num_kv_heads``: q, k, v, o forward; q, k, v, dO, dQ for dQ; q, k, v,
dO, dK, dV for dK/dV.  Each kernel's work counts once a step, a
rematerialised layer's forward too, as the sibling says why.  Nothing to
read where the configuration has no such lists or the trace holds none
of the kernels."""

import os

from chipbench.harness import load_module

MHA = load_module(os.path.join(os.path.dirname(__file__), "attn_roofline_pct.py"))
KERNELS = MHA.KERNELS


def pairs(t: int, window=None) -> int:
    """Query-key pairs one row and head attends over ``t`` positions."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def step_work(config: dict, rows: int) -> dict:
    """``{kernel: (operations, bytes)}`` of one training step."""
    kw = config["model"]["kwargs"]
    if "num_heads_per_layer" not in kw:
        return {}
    t, d, hkv = config["input"]["seq_len"], kw["head_dim"], kw["num_kv_heads"]
    head_pairs = q = kv = 0
    for l in range(kw["layer_offset"], kw["layer_offset"] + kw["num_layers"]):
        h = kw["num_heads_per_layer"][l]
        sliding = kw["layer_types"][l] == "sliding_attention"
        head_pairs += h * pairs(t, kw["sliding_window"] if sliding else None)
        q += rows * t * h * d * 2      # a tensor at the query heads, bf16
        kv += rows * t * hkv * d * 2   # one at the key-value heads
    product = 2 * rows * head_pairs * d
    return {
        KERNELS[0]: (2 * product, 2 * q + 2 * kv),
        KERNELS[1]: (3 * product, 3 * q + 2 * kv),
        KERNELS[2]: (4 * product, 2 * q + 4 * kv),
    }


def read(ctx):
    return MHA.share(ctx, step_work(
        ctx["config"], ctx["traffic"]["global_batch"] // ctx["chips"]))
