"""Kernels: the flash-attention kernels' share of their roofline in a
configuration with EVA attention (``window_size``, ``chunk_size``,
``heads_held``), over both of its parts and over the traced steps;
``attn_roofline_pct`` is its sibling for latent attention and holds how
a kernel's least time is taken from its work.

What a step needs, per layer, row and held head, at head size ``dim /
num_heads``: the exact part is ``T / window`` causal squares of ``window
(window + 1) / 2`` query-key pairs, the summarised part ``(window /
chunk) * window * (0 + 1 + .. + (T / window - 1))`` pairs of a query
with a chunk's summary.  Over those pairs the forward kernel makes 2
products, the dQ kernel 3, the dK/dV kernel 4, as the sibling counts
them: each kernel's work once a step, a rematerialised layer's forward
too.  Bytes are each operand and result once, in bfloat16: for the
exact part q, k, v, o (and dO, dQ or dK, dV) at ``T`` rows; for the
summarised part q and o (dO, dQ) at the ``T - window`` queries that have
summaries to see, and the summaries (their gradients) at the ``T /
chunk - window / chunk`` chunks that are seen.
Nothing to read where the configuration has no such attention or the
trace holds none of the kernels; every one it holds is read, however
little time it took (``fdtpu_flash_dq`` is not among the cell's ten
kinds of operation with most time)."""

import os

from chipbench.harness import load_module

MHA = load_module(os.path.join(os.path.dirname(__file__), "attn_roofline_pct.py"))
KERNELS = MHA.KERNELS


def pairs(t: int, window: int, chunk: int) -> tuple:
    """``(exact, summarised)`` attended pairs of one row and head."""
    nw = t // window
    return (nw * (window * (window + 1) // 2),
            (window // chunk) * window * (nw * (nw - 1) // 2))


def step_work(config: dict, rows: int) -> dict:
    """``{kernel: (operations, bytes)}`` of one training step."""
    kw = config["model"]["kwargs"]
    if "window_size" not in kw or "chunk_size" not in kw:
        return {}
    t, window, chunk = config["input"]["seq_len"], kw["window_size"], kw["chunk_size"]
    d = kw["dim"] // kw["num_heads"]
    held = (kw.get("heads_held") or (0, kw["num_heads"]))[1]
    calls = rows * held * kw["num_layers"]
    per_product = 2 * sum(pairs(t, window, chunk)) * d * calls
    row = calls * d * 2  # one position of one tensor, bf16
    exact, late, seen = t * row, (t - window) * row, (t - window) // chunk * row
    return {
        KERNELS[0]: (2 * per_product, 4 * exact + 2 * late + 2 * seen),
        KERNELS[1]: (3 * per_product, 5 * exact + 3 * late + 2 * seen),
        KERNELS[2]: (4 * per_product, 6 * exact + 2 * late + 4 * seen),
    }


def read(ctx):
    return MHA.share(ctx, step_work(
        ctx["config"], ctx["traffic"]["global_batch"] // ctx["chips"]))
