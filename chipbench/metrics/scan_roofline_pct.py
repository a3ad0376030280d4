"""Kernels: the selective scan's kernels' share of their roofline in a
configuration with Mamba layers (a ``mamba`` group; which of the held
layers are Mamba layers, ``configs/phi4_mini_flash.py::kind_of`` says by
their published index), over the traced steps; ``attn_roofline_pct`` holds how a kernel's least time
is taken from its work and turned into a share.

The scan does no work on the MXU and the peaks table has no vector
peak, so a kernel's least time is its bytes over the HBM bandwidth:
the algorithm's bytes, each operand and result once, in float32 (what
the layer hands over): the forward (``fdtpu_scan_fwd``) reads ``u``,
``delta`` (``[rows, T, d_inner]``), ``B`` and ``C`` (``[rows, T,
d_state]``) and writes ``y``; the backward (``fdtpu_scan_bwd``) reads
those four and ``dy`` and writes the gradients of the four.  Each
kernel's work counts once a step, a rematerialised layer's forward too;
the kept states and a tile's re-reads are not the algorithm's and are
left out.  Nothing to read where the configuration has no Mamba layer
or the trace holds neither kernel."""

import os

from chipbench.harness import load_module

HERE = os.path.dirname(__file__)
MHA = load_module(os.path.join(HERE, "attn_roofline_pct.py"))
#: the configuration's reference, which says each published layer's kind
SAMBAY = load_module(os.path.normpath(
    os.path.join(HERE, "..", "configs", "phi4_mini_flash.py")))
KERNELS = ("fdtpu_scan_fwd", "fdtpu_scan_bwd")


def held_kinds(config: dict) -> list:
    """The kind of each held layer (``mamba``, ``window``, ``full``,
    ``gmu``, ``cross``); none where the configuration has no Mamba
    layers."""
    if "mamba" not in config or "layer_offset" not in config:
        return []
    return [SAMBAY.kind_of(config, config["layer_offset"] + i)
            for i in range(config["num_hidden_layers"])]


def step_work(config: dict, rows: int) -> dict:
    """``{kernel: (operations, bytes)}`` of one training step."""
    layers = held_kinds(config).count("mamba")
    if not layers:
        return {}
    kw = config["model"]["kwargs"]
    positions = rows * config["input"]["seq_len"] * layers * 4  # float32
    wide = positions * kw["expand"] * kw["dim"]   # one [rows, T, d_inner]
    narrow = positions * kw["d_state"]            # one [rows, T, d_state]
    return {KERNELS[0]: (0, 3 * wide + 2 * narrow),
            KERNELS[1]: (0, 5 * wide + 4 * narrow)}


def read(ctx):
    return MHA.share(ctx, step_work(
        ctx["config"], ctx["traffic"]["global_batch"] // ctx["chips"]))
