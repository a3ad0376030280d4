#!/usr/bin/env python
"""The selective scan alone: one Mamba layer's ``selective_scan`` at the
``phi4_mini_flash`` cell's widths (2 rows x 4,096 positions x 5,120
channels, 16 states, float32), forward and forward-plus-backward (all six
gradients), each the median of repeated calls on the host clock.

    python benchmarks/scan_bench.py                        # the TPU
    python benchmarks/scan_bench.py --module OTHER/pallas_scan.py \\
        --module fluxdistributed_tpu/ops/pallas_scan.py    # compare bodies

``--module`` times another copy of ``ops/pallas_scan.py`` (another
commit's, a variant's), loaded inside this checkout's package; it may be
given more than once.  One JSON line a module: ``fwd_ms``, ``fwdbwd_ms``
(the median of ``REPS`` calls), their difference ``bwd_ms``, ``rel_err``
(the kernels against the plain ``lax.scan`` at 1 x 600 x 1,024: three
chunks, the last part padding, two and four channel blocks; ``y`` and
each of the six gradients by name) and ``max_rel_err``, its largest, the
tiles the gauges report and the device.  A backend other than a TPU is
an error: the interpreter's times say nothing of the chip's.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

#: calls a time is the median of
REPS = 20
#: ``y`` and the six gradients, in the order ``rel_err`` compares them
OUTPUTS = ("y", "u", "delta", "A", "B", "C", "D")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def load(path):
    """``path``, a copy of ``ops/pallas_scan.py``, as a module of this
    checkout's ``fluxdistributed_tpu.ops`` (its relative imports resolve
    there); the checkout's own module for None."""
    if path is None:
        from fluxdistributed_tpu.ops import pallas_scan
        return pallas_scan
    name = "fluxdistributed_tpu.ops._scan_bench_%d" % abs(hash(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def median_ms(fn, args, reps, warmup=3):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def inputs(rows, t, c, n, seed=0):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (rows, t, c))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (rows, t, c)) - 2.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (c, n)))
    b, cc = (jax.random.normal(k, (rows, t, n)) for k in ks[3:5])
    d = jax.random.normal(ks[5], (c,))
    return (u, delta, a, b, cc, d), jax.random.normal(ks[6], (rows, t, c))


def rel_err(mod, shape):
    """The kernels against the module's plain ``lax.scan`` on fresh inputs
    of ``shape`` (rows, T, C): for ``y`` and each of the six gradients,
    by name, its max-abs difference over its reference's max-abs."""
    import jax
    import jax.numpy as jnp

    args, w = inputs(*shape, 16, seed=1)

    def outs(fn):
        loss = lambda *a: jnp.sum(fn(*a) * w)  # noqa: E731
        return (fn(*args), *jax.grad(loss, argnums=range(6))(*args))

    return {name: float(jnp.max(jnp.abs(g - h)) / jnp.max(jnp.abs(h)))
            for name, g, h in zip(OUTPUTS, outs(mod.selective_scan),
                                  outs(mod.selective_scan_xla))}


def bench(mod, args, w, reps=REPS, check=(1, 600, 1024)):
    import jax
    import jax.numpy as jnp

    from fluxdistributed_tpu.obs import get_registry

    dims = ("chunk", "channels_fwd", "channels_bwd", "columns_per_load")
    tiles = get_registry().gauge("fdtpu_scan_tiles", labelnames=("dim",))
    for dim in dims:  # what a module that sets no such gauge leaves: 0
        tiles.labels(dim).set(0)
    err = rel_err(mod, check)
    fwd = jax.jit(mod.selective_scan)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(mod.selective_scan(*a) * w), argnums=range(6)))
    out = {"fwd_ms": median_ms(fwd, args, reps),
           "fwdbwd_ms": median_ms(grads, args, reps)}
    out["bwd_ms"] = out["fwdbwd_ms"] - out["fwd_ms"]
    out["rel_err"] = err
    out["max_rel_err"] = max(err.values())
    out["tiles"] = {dim: tiles.value(dim) for dim in dims}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--module", action="append", default=None,
                    help="a copy of ops/pallas_scan.py to time (repeatable)")
    a = ap.parse_args()

    import jax

    rows, t, c = 2, 4096, 5120
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"error": "no TPU", "platform": dev.platform}))
        return 1
    args, w = inputs(rows, t, c, 16)
    for path in a.module or [None]:
        row = {"metric": "selective scan, one Mamba layer",
               "module": path or "fluxdistributed_tpu/ops/pallas_scan.py",
               "shape": {"rows": rows, "T": t, "C": c, "N": 16},
               **bench(load(path), args, w),
               "device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": jax.device_count()}}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
