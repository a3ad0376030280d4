#!/usr/bin/env python
"""Attention-core microbenchmark: Pallas flash vs XLA blockwise vs dense.

The framework's hand-written hot-op (ops/pallas_attention.py) exists to
beat the dense core's HBM behavior at long T; this measures whether it
does on real hardware — per-core ms and achieved TFLOP/s for forward and
forward+backward at growing sequence lengths, causal, bf16.

    python benchmarks/attention_bench.py                    # TPU
    python benchmarks/attention_bench.py --platform cpu \
        --seqlens 128 --batch 1 --heads 2 --dim 32          # smoke

Attention FLOPs ≈ 4·B·H·T²·D forward (q·kᵀ + p·v), halved when causal;
backward ≈ 2.5× forward.  Run under `timeout`, never kill a TPU client.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)

# the shared pure-function timing protocol (3-step post-compile warmup),
# so attention rows are measured like every other benchmark row
from train_step_segments import timeit  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--seqlens", default="1024,2048,4096")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--window", type=int, default=None,
                    help="add a windowed pallas-flash row (block-skip "
                         "FLOPs saving at long T)")
    ap.add_argument("--decode", action="store_true",
                    help="run the flash-DECODE section instead: one "
                         "query row per slot vs the serve cache layouts "
                         "(dense cursor / windowed ring + sinks / paged "
                         "pool), pallas fast path vs the engine's XLA "
                         "gather+mask path")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode section: concurrent slots (batch rows)")
    ap.add_argument("--max-len", type=int, default=2048,
                    help="decode section: reserved cache rows per slot")
    ap.add_argument("--live", type=int, default=128,
                    help="decode section: live tokens per slot (the "
                         "cursor position — the fast path's win scales "
                         "with max-len/live)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="decode section: paged pool rows per block")
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    from fluxdistributed_tpu.ops.attention import (
        blockwise_attention, dot_product_attention,
    )
    from fluxdistributed_tpu.ops.pallas_attention import flash_attention

    if args.decode:
        return decode_main(args, jax, jnp)

    B, H, D = args.batch, args.heads, args.dim
    blk = args.block
    cores = [
        ("dense", jax.jit(lambda q, k, v: dot_product_attention(q, k, v, causal=True))),
        ("blockwise-xla", jax.jit(
            lambda q, k, v: blockwise_attention(q, k, v, block_size=blk, causal=True))),
        ("pallas-flash", jax.jit(
            lambda q, k, v: flash_attention(q, k, v, True, blk, blk))),
    ]
    if args.window is not None:
        w = args.window
        if w < 1:
            raise SystemExit(f"--window must be >= 1, got {w}")
        cores.append((f"pallas-flash-w{w}", jax.jit(
            lambda q, k, v: flash_attention(q, k, v, True, blk, blk, w))))
    grads = {
        name: jax.jit(jax.grad(lambda q, k, v, f=fn: jnp.sum(f(q, k, v).astype(jnp.float32)),
                               argnums=(0, 1, 2)))
        for name, fn in cores
    }

    rows = []
    for t in [int(s) for s in args.seqlens.split(",")]:
        rng = np.random.default_rng(0)
        q, k, v = (
            jnp.asarray(rng.normal(0, 1, (B, t, H, D)), jnp.bfloat16)
            for _ in range(3)
        )
        fwd_flops = 4 * B * H * t * t * D / 2  # causal halves the score work
        if args.window is not None:
            # the windowed kernel's USEFUL work is the band, not T^2/2:
            # sum_q min(q+1, W) attended keys (otherwise its TFLOP/s
            # column would overstate by ~T/W and could exceed chip peak)
            w = min(args.window, t)
            attended = w * (w + 1) // 2 + max(t - w, 0) * w
            fwd_flops_windowed = 4 * B * H * D * attended
        for name, fn in cores:
            if name == "dense" and t > 8192:
                continue  # T^2 scores in HBM; keep the sweep bounded
            dt = timeit(fn, q, k, v, n=args.iters)
            dtg = timeit(grads[name], q, k, v, n=max(5, args.iters // 2))
            fl = fwd_flops_windowed if name.startswith("pallas-flash-w") else fwd_flops
            rows.append({
                "core": name, "T": t,
                "fwd_ms": round(dt * 1e3, 3),
                "fwd_tflops": round(fl / dt / 1e12, 2),
                "fwdbwd_ms": round(dtg * 1e3, 3),
            })
            print(json.dumps(rows[-1]), flush=True)

    print(json.dumps({
        "metric": "attention-core microbench (causal, bf16)",
        "config": {"B": B, "H": H, "D": D, "block": blk},
        "platform": jax.devices()[0].platform,
        "rows": rows,
    }))


def decode_main(args, jax, jnp):
    """Flash-decode vs the engine's XLA decode attention, op-level.

    One query row per slot against each serve cache layout, at a LIVE
    length far below the reserved rows — the regime continuous batching
    actually runs in.  The XLA side is exactly what the engine's model
    computes per step (full-cache mask; paged adds the pool gather);
    the pallas side is `ops.pallas_decode` under its default impl
    resolution (compiled kernel on TPU, the same block-walk schedule as
    an XLA fallback elsewhere — both skip dead blocks/pages, neither
    gathers dead cache).
    """
    import numpy as np

    from fluxdistributed_tpu.ops.attention import dot_product_attention
    from fluxdistributed_tpu.ops.pallas_decode import (
        flash_decode, flash_decode_paged, resolve_decode_impl,
    )

    B, H, D = args.slots, args.heads, args.dim
    R, live, bs = args.max_len, min(args.live, args.max_len), args.kv_block_size
    window, sinks = args.window or 256, 4
    rng = np.random.default_rng(0)
    dt = jnp.float32 if jax.devices()[0].platform == "cpu" else jnp.bfloat16

    def arr(*shape):
        return jnp.asarray(rng.normal(0, 1, shape), dt)

    q = arr(B, 1, H, D)
    idx = jnp.full((B,), live - 1, jnp.int32)
    rows = []

    def measure(name, xla_fn, pal_fn, operands, nbytes_live):
        # operands are ARGUMENTS (not closures): constants would let
        # XLA fold small cases away and time nothing
        tx = timeit(jax.jit(xla_fn), *operands, n=args.iters)
        tp = timeit(jax.jit(pal_fn), *operands, n=args.iters)
        rows.append({
            "layout": name,
            "xla_ms": round(tx * 1e3, 3),
            "pallas_ms": round(tp * 1e3, 3),
            "pallas_speedup_x": round(tx / tp, 2),
            "live_kv_bytes": int(nbytes_live),
        })
        print(json.dumps(rows[-1]), flush=True)

    # --- dense plain: cursor block-skip vs full-R mask --------------------
    k, v = arr(B, R, H, D), arr(B, R, H, D)

    def dense_xla(q, k, v, idx):
        allow = (jnp.arange(R)[None, :] <= idx[:, None])[:, None, None, :]
        return dot_product_attention(q, k, v, mask=allow)

    measure(
        f"dense R={R} live={live}",
        dense_xla,
        lambda q, k, v, idx: flash_decode(q, k, v, idx),
        (q, k, v, idx),
        2 * B * live * H * D * jnp.dtype(dt).itemsize,
    )

    # --- windowed ring + sinks (compact ring, slot_pos band mask) ---------
    ring_rows = sinks + window + bs
    kr, vr = arr(B, ring_rows, H, D), arr(B, ring_rows, H, D)
    sp0 = np.full((ring_rows,), -1, np.int32)
    ring = ring_rows - sinks
    cur = live - 1
    for p in range(live):  # the ring's write layout at cursor `cur`
        slot = p if p < sinks else sinks + (p - sinks) % ring
        if p < sinks or p > cur - ring:
            sp0[slot] = p
    sp = jnp.asarray(np.tile(sp0, (B, 1)))

    def ring_xla(q, kr, vr, sp, idx):
        qg = idx[:, None]
        allow = (sp >= 0) & (sp <= qg)
        allow &= (sp > qg - window) | (sp < sinks)
        return dot_product_attention(q, kr, vr, mask=allow[:, None, None, :])

    measure(
        f"ring window={window}+sinks={sinks}",
        ring_xla,
        lambda q, kr, vr, sp, idx: flash_decode(
            q, kr, vr, idx, slot_pos=sp, window=window, sinks=sinks),
        (q, kr, vr, sp, idx),
        2 * B * min(live, ring_rows) * H * D * jnp.dtype(dt).itemsize,
    )

    # --- paged pool: page-table walk vs gather + full mask ----------------
    pages = -(-R // bs)
    live_pages = -(-live // bs)
    nb = B * pages  # full-capacity pool
    kp, vp = arr(nb, bs, H, D), arr(nb, bs, H, D)
    pt = np.full((B, pages), -1, np.int32)
    for bb in range(B):  # live prefix bound, everything else unbound
        pt[bb, :live_pages] = bb * pages + np.arange(live_pages)
    pt = jnp.asarray(pt)

    def paged_xla(q, kp, vp, pt, idx):
        # the engine model's XLA path: gather the slot view, mask it
        gk = kp[jnp.maximum(pt, 0)].reshape(B, pages * bs, H, D)
        gv = vp[jnp.maximum(pt, 0)].reshape(B, pages * bs, H, D)
        allow = (jnp.arange(pages * bs)[None, :] <= idx[:, None])
        allow &= jnp.repeat(pt >= 0, bs, axis=1)
        return dot_product_attention(q, gk, gv, mask=allow[:, None, None, :])

    measure(
        f"paged R={R} bs={bs} live={live}",
        paged_xla,
        lambda q, kp, vp, pt, idx: flash_decode_paged(q, kp, vp, pt, idx),
        (q, kp, vp, pt, idx),
        2 * B * live_pages * bs * H * D * jnp.dtype(dt).itemsize,
    )

    best = max(rows, key=lambda r: r["pallas_speedup_x"])
    print(json.dumps({
        "metric": f"flash-decode vs XLA decode attention "
                  f"({jax.devices()[0].platform}, "
                  f"impl={resolve_decode_impl(None)}, B={B}, H={H}, D={D}, "
                  f"R={R}, live={live})",
        "value": best["pallas_speedup_x"],
        "unit": f"x faster than the XLA decode path (best: {best['layout']})",
        "rows": rows,
    }))


if __name__ == "__main__":
    main()
