"""Kernels: the flash-attention kernels' share of their roofline in a
configuration with grouped-query attention by layer kind
(``num_kv_heads`` key-value heads under ``num_heads`` query heads, in
the layers whose ``layer_types`` entry is ``full_attention``), over the
traced steps; ``attn_roofline_pct`` is its sibling for latent attention
and holds how a kernel's work is counted and turned into a least time.

The operations are those of as many full heads: every query head makes
its own products over a causal square of ``T (T + 1) / 2`` pairs at head
size ``dim / num_heads``.  The bytes are the sibling's with ``k``,
``v`` and their gradients at ``num_kv_heads``: 2 such tensors in the
forward and the dQ kernel, 4 in the dK/dV kernel.  Each kernel's work
counts once a step, a rematerialised layer's forward too, as the sibling
says why.  Nothing to read where the configuration has no such layers
or the trace holds none of the kernels."""

import os

from chipbench.harness import load_module

MHA = load_module(os.path.join(os.path.dirname(__file__), "attn_roofline_pct.py"))
KERNELS = MHA.KERNELS
#: tensors of key-value size a kernel reads or writes: k, v; k, v; k, v, dK, dV
KV_TENSORS = (2, 2, 4)


def step_work(config: dict, rows: int) -> dict:
    """``{kernel: (operations, bytes)}`` of one training step."""
    kw = config["model"]["kwargs"]
    if "num_kv_heads" not in kw or "layer_types" not in kw:
        return {}
    layers = list(kw["layer_types"]).count("full_attention")
    if not layers:
        return {}
    h, d = kw["num_heads"], kw["dim"] // kw["num_heads"]
    # the sibling's count of as many layers of full heads of this size
    full = MHA.step_work({"input": config["input"], "model": {"kwargs": {
        "qk_nope_head_dim": d, "qk_rope_head_dim": 0, "num_heads": h,
        "num_layers": layers}}}, rows)
    tensor = rows * config["input"]["seq_len"] * h * d * 2 * layers  # bf16
    absent = tensor * (1.0 - kw["num_kv_heads"] / h)
    return {name: (full[name][0], full[name][1] - kv * absent)
            for name, kv in zip(KERNELS, KV_TENSORS)}


def read(ctx):
    return MHA.share(ctx, step_work(
        ctx["config"], ctx["traffic"]["global_batch"] // ctx["chips"]))
