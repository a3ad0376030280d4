"""Kernels: the flash-attention kernels' share of their roofline, over
the traced steps.  A kernel's least time for one step is the larger of
its operations over the chip's bf16 peak and its bytes over the HBM
bandwidth; the share is that, times the whole steps the traced slice
holds (``trace.py::step_cycles``), over the seconds the kernel ran
inside those steps, summed over the three kernels
(``fdtpu_flash_fwd``, ``fdtpu_flash_dq``, ``fdtpu_flash_dkv``:
``ops/pallas_attention.py::KERNEL_NAMES``), each read from the trace's
``kernels`` however little time it took.  Nothing to read where the
configuration has no latent attention or the trace holds none of them.

What a step needs, each kernel's work ONCE: per layer, row and head, a
causal square of ``T (T + 1) / 2`` query-key pairs at head size ``D``;
the forward kernel makes 2 products over them (scores, values), the dQ
kernel 3 (scores, dP, dQ), the dK/dV kernel 4 (scores, dP, dV, dK).  A
rematerialised layer counts one forward: the yardstick is the work the
algorithm needs, not what an implementation recomputes, so a program
that runs the forward again reads below the roofline for it and one that
stops doing so can never read above 100.  Bytes are each operand and
result once, in the compute type: q, k, v, o for the forward; q, k, v,
dO, dQ for dQ; q, k, v, dO, dK, dV for dK/dV; the rows' statistics (4
bytes a query and head) are left out."""

KERNELS = ("fdtpu_flash_fwd", "fdtpu_flash_dq", "fdtpu_flash_dkv")


def step_work(config: dict, rows: int) -> dict:
    """``{kernel: (operations, bytes)}`` of one training step."""
    kw = config["model"]["kwargs"]
    if "qk_nope_head_dim" not in kw:
        return {}
    t, h = config["input"]["seq_len"], kw["num_heads"]
    d = kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]
    layers = kw["num_layers"] + kw.get("num_nextn_predict_layers", 0)
    pairs = t * (t + 1) // 2
    per_product = 2 * pairs * d * rows * h * layers
    tensor = rows * t * h * d * 2 * layers  # bf16
    return {
        KERNELS[0]: (2 * per_product, 4 * tensor),
        KERNELS[1]: (3 * per_product, 5 * tensor),
        KERNELS[2]: (4 * per_product, 6 * tensor),
    }


def least_seconds(work, peaks: dict) -> float:
    ops, nbytes = work
    return max(ops / (peaks["bf16_tflops"] * 1e12),
               nbytes / (peaks["hbm_gb_per_s"] * 1e9))


def share(ctx, work: dict):
    """The roofline share of the kernels in ``work`` that the trace
    holds; None where it holds none of them or counts no step."""
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    seen = {n: s for n, s in t["kernels"].items() if n in work and s > 0}
    if not seen:
        return None
    least = sum(least_seconds(work[n], ctx["peaks"]) for n in seen)
    return 100.0 * least * t["steps"] / sum(seen.values())


def read(ctx):
    return share(ctx, step_work(ctx["config"],
                                ctx["traffic"]["global_batch"] // ctx["chips"]))
