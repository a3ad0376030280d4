"""Kernels: the flash-attention kernels' share of their roofline, over
the traced steps.  A kernel's least time for one step is the larger of
its operations over the chip's bf16 peak and its bytes over the HBM
bandwidth; the share is that, times the traced steps, over the seconds
the trace holds under the kernel's name, summed over the three kernels
(``fdtpu_flash_fwd``, ``fdtpu_flash_dq``, ``fdtpu_flash_dkv``:
``ops/pallas_attention.py::KERNEL_NAMES``).  Nothing to read where the
configuration has no latent attention or the trace holds none of them
among its ten kinds of operation.

What a step needs, recomputation included: per layer, row and head, a
causal square of ``T (T + 1) / 2`` query-key pairs at head size ``D``;
the forward kernel makes 2 products over them (scores, values) and runs
twice where the layer is rematerialised, the dQ kernel 3 (scores, dP,
dQ), the dK/dV kernel 4 (scores, dP, dV, dK).  Bytes are each operand
and result once, in the compute type: q, k, v, o for the forward; q, k,
v, dO, dQ for dQ; q, k, v, dO, dK, dV for dK/dV; the rows' statistics
(4 bytes a query and head) are left out."""

KERNELS = ("fdtpu_flash_fwd", "fdtpu_flash_dq", "fdtpu_flash_dkv")


def step_work(config: dict, rows: int) -> dict:
    """``{kernel: (operations, bytes)}`` of one training step."""
    kw = config["model"]["kwargs"]
    if "qk_nope_head_dim" not in kw:
        return {}
    t, h = config["input"]["seq_len"], kw["num_heads"]
    d = kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]
    layers = kw["num_layers"] + kw.get("num_nextn_predict_layers", 0)
    forwards = 2 if kw.get("remat") else 1
    pairs = t * (t + 1) // 2
    per_product = 2 * pairs * d * rows * h * layers
    tensor = rows * t * h * d * 2 * layers  # bf16
    return {
        KERNELS[0]: (forwards * 2 * per_product, forwards * 4 * tensor),
        KERNELS[1]: (3 * per_product, 5 * tensor),
        KERNELS[2]: (4 * per_product, 6 * tensor),
    }


def least_seconds(work, peaks: dict) -> float:
    ops, nbytes = work
    return max(ops / (peaks["bf16_tflops"] * 1e12),
               nbytes / (peaks["hbm_gb_per_s"] * 1e9))


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    work = step_work(ctx["config"], ctx["traffic"]["global_batch"] // ctx["chips"])
    seen = {n: s for n, s in t["device_ops"] if n in work and s > 0}
    if not seen:
        return None
    least = sum(least_seconds(work[n], ctx["peaks"]) for n in seen)
    return 100.0 * least * t["steps"] / sum(seen.values())
