#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one pass over the main path through the entry points a user
calls (``prepare_training`` → ``train``, exactly as README.md shows), at
the full width of ResNet-50 with random weights made from a seed:

    python chip_smoke.py            # one chip: train, kernels, cache
    python chip_smoke.py --chips 4  # ONLY the data-parallel path over four
                                    # chips and its one-device comparison

It forces no platform and fails — non-zero exit, no ``ok`` line — when
``jax.devices()[0].platform`` is not ``tpu``.  Every phase failure
propagates; nothing is caught and carried past.  The step times, compile
seconds and memory peaks it prints are start-up facts labelled with the
device kind, not a benchmark.

The last line of stdout is the one JSON object the driver reads:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

``tests/test_chip_smoke.py`` rehearses the same phases at a tiny size on
the CPU by passing ``main`` a :class:`Size` (a function argument, not a
CLI option).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time


@dataclasses.dataclass(frozen=True)
class Size:
    """What a run drives.  The defaults ARE the chip run; a test passes
    a smaller one (and the platform it runs on)."""

    platform: str = "tpu"  # jax.devices()[0].platform must equal this
    # -- train phase: ResNet-50 / synthetic ImageNet through the trainer
    model: str = "resnet50"
    classes: int = 1000
    image: int = 224
    per_chip_batch: int = 256
    steps: int = 12  # optimizer steps after the warm-up step
    params_millions: float | None = 25.6
    # -- kernel phase: GPT-2-small attention widths, ResNet-50-sized Adam
    heads: int = 12
    kv_heads: int = 4  # the GQA case
    head_dim: int = 64
    attn_batch: int = 4
    seqs: tuple = (1024, 2048)
    window: int = 256
    sinks: int = 4
    decode_batch: int = 8
    cache_rows: int = 1024
    pool_blocks: int = 512
    pool_block_rows: int = 16
    adam_elems: int = 25_557_032
    # -- --chips 4 phase: data-parallel vs the same global batch on one
    dp_model: str = "resnet50"
    dp_batch: int = 256
    dp_steps: int = 5


FULL = Size()

# Tolerances, as normalised max error  max|got - want| / max|want|.
# bf16 carries 8 mantissa bits (eps 7.8e-3): kernel and reference both
# accumulate in f32 but round P to bf16 at different points (the kernel
# before normalising, the reference after) and round the output once
# more, so a few eps is the honest bound; a wrong mask, block or scale
# misses it by 1e-1 or more.  The Adam kernel is f32 elementwise math
# against the same expression in XLA: only sqrt/divide rounding differs.
TOL_BF16 = 3e-2
TOL_F32 = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def require_device(platform: str, chips: int) -> dict:
    """Fail before any work unless jax found ``chips`` devices of
    ``platform``.  Returns the device as jax reports it."""
    import jax

    devs = jax.devices()
    got = devs[0].platform
    if got != platform:
        raise SystemExit(
            f"chip_smoke: jax found platform {got!r}, not {platform!r} — "
            "this script proves the chip path and never falls back")
    if chips == 4 and len(devs) != 4:
        raise SystemExit(
            f"chip_smoke --chips 4: jax found {len(devs)} device(s), need 4")
    return {"platform": got, "kind": devs[0].device_kind, "count": len(devs)}


def _peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats.get("peak_bytes_in_use", 0))


class _Capture:
    """The trainer's Logger protocol: keeps every per-step training loss
    and the process's compile count at the moment it was logged."""

    def __init__(self):
        self.losses: list[float] = []
        self.compiles: list[int] = []
        self.times: list[float] = []

    def log(self, metrics, step):
        from fluxdistributed_tpu import compilation

        if "train_step_loss" in metrics:
            self.losses.append(float(metrics["train_step_loss"]))
            self.compiles.append(compilation.compile_metrics()["compiles"])
            self.times.append(time.perf_counter())

    def info(self, msg):
        pass


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------


def run_train(size: Size, device: dict) -> None:
    """ResNet-50 on synthetic ImageNet through prepare_training → train."""
    import jax
    import numpy as np

    from fluxdistributed_tpu import compilation, models, optim
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.train import prepare_training, train

    kind = device["kind"]
    batch = size.per_chip_batch * device["count"]
    # as many rows as one batch holds: every step redraws (with noise)
    # from the same few samples, so the loss must fall within a few steps
    dataset = SyntheticDataset(
        nsamples=batch, nclasses=size.classes,
        shape=(size.image, size.image, 3), seed=0)
    model = getattr(models, size.model)(num_classes=size.classes)
    t0 = time.perf_counter()
    task = prepare_training(
        model, dataset, optim.momentum(0.01, 0.9),
        batch_size=batch, cycles=size.steps, seed=0,
        cache_dir=compilation.resolve_cache_dir(), warmup=True)
    cm = compilation.compile_metrics()
    log(f"[train] {size.model} prepared + warmed up in "
        f"{time.perf_counter() - t0:.1f}s on {kind} "
        f"({cm['compiles']} compiles, {cm['compile_seconds']}s compiling, "
        f"cache hits {cm['cache_hits']} misses {cm['cache_misses']})")

    nparams = sum(int(np.prod(p.shape))
                  for p in jax.tree.leaves(task.state.params))
    if size.params_millions is not None:
        assert abs(nparams / 1e6 - size.params_millions) < 0.1, nparams
    for leaf in jax.tree.leaves(task.state.params):
        assert {d.platform for d in leaf.devices()} == {size.platform}, leaf

    cap = _Capture()
    # eval_every=1: the loop logs each step's training loss (and runs the
    # compiled eval step on the chip too)
    train(task, print_every=0, eval_every=1, logger=cap)

    losses = cap.losses
    assert len(losses) == size.steps, (len(losses), size.steps)
    assert all(math.isfinite(l) for l in losses), losses
    assert abs(losses[0] - math.log(size.classes)) < 0.5, losses[0]
    assert losses[-1] < losses[0], losses
    assert int(task.state.step) == size.steps, int(task.state.step)
    assert task.num_missed == 0, task.num_missed
    # the first step compiles what is left (eval, host-side scalars);
    # after it the loop must not compile again
    assert cap.compiles[-1] == cap.compiles[0], cap.compiles
    steps = np.diff(cap.times)
    log(f"[train] {size.steps} steps, batch {batch}, {nparams / 1e6:.2f}M "
        f"params: loss {losses[0]:.4f} -> {losses[-1]:.4f}; median "
        f"step+eval wall {np.median(steps):.3f}s on {kind} "
        f"(loader and per-step eval included: not a rate)")
    log(f"[train] device memory peak on {kind}: {_peak_bytes()} bytes")


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def _rand(key, shape, dtype):
    import jax

    return jax.random.normal(key, shape, dtype)


def _ring_slot_pos(cursors, rows: int, sinks: int, window: int):
    """The ring cache's position side buffer for per-slot ``cursors``:
    the first ``sinks`` rows hold positions 0..sinks-1, the rest hold
    the newest positions ``p`` at row ``sinks + (p - sinks) % window``;
    -1 marks a row not written yet."""
    import numpy as np

    out = np.full((len(cursors), rows), -1, np.int32)
    for b, c in enumerate(cursors):
        for p in range(min(sinks, c + 1)):
            out[b, p] = p
        for p in range(max(sinks, c - window + 1), c + 1):
            out[b, sinks + (p - sinks) % window] = p
    return out


def _kernel_cases(size: Size, impl: str):
    """``(name, kernel_fn, reference_fn, args, tol)`` per Pallas entry
    point at the widths of ``size``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fluxdistributed_tpu.ops import pallas_attention as pa
    from fluxdistributed_tpu.ops import pallas_decode as pd
    from fluxdistributed_tpu.ops.attention import dot_product_attention
    from fluxdistributed_tpu.parallel import zero1_fused as zf

    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    h, hkv, d = size.heads, size.kv_heads, size.head_dim

    def grads(attn):
        # a random cotangent w exercises dQ, dK and dV together
        def f(q, k, v, w):
            return jax.grad(
                lambda q, k, v: (attn(q, k, v).astype(jnp.float32) * w).sum(),
                argnums=(0, 1, 2))(q, k, v)
        return f

    def flash(**kw):
        return lambda q, k, v: pa.flash_attention(
            q, k, v, True, 128, 128, kw.get("window"), kw.get("sinks", 0))

    def dense(**kw):
        return lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, **kw)

    cases = []
    for t in size.seqs:
        q, k, v = (_rand(next(keys), (size.attn_batch, t, h, d), bf)
                   for _ in range(3))
        w = _rand(next(keys), (size.attn_batch, t, h, d), jnp.float32)
        cases.append((f"flash_fwd T={t}", flash(), dense(), (q, k, v),
                      TOL_BF16))
        cases.append((f"flash_bwd T={t}", grads(flash()), grads(dense()),
                      (q, k, v, w), TOL_BF16))
    t0, t1 = size.seqs[0], size.seqs[-1]
    q = _rand(next(keys), (size.attn_batch, t0, h, d), bf)
    k, v = (_rand(next(keys), (size.attn_batch, t0, hkv, d), bf)
            for _ in range(2))
    w = _rand(next(keys), q.shape, jnp.float32)
    cases.append((f"flash_bwd GQA {h}/{hkv} T={t0}", grads(flash()),
                  grads(dense()), (q, k, v, w), TOL_BF16))
    q, k, v = (_rand(next(keys), (size.attn_batch, t1, h, d), bf)
               for _ in range(3))
    w = _rand(next(keys), q.shape, jnp.float32)
    ws = dict(window=size.window, sinks=size.sinks)
    cases.append((f"flash_bwd window={size.window} sinks={size.sinks} "
                  f"T={t1}", grads(flash(**ws)), grads(dense(**ws)),
                  (q, k, v, w), TOL_BF16))

    # decode: one query row per slot against its cache; the reference is
    # the same call through the XLA block walk (impl="xla")
    b, r = size.decode_batch, size.cache_rows
    cursors = np.linspace(r // 8, r - 1, b).astype(np.int32)
    idx = jnp.asarray(cursors)
    qd = _rand(next(keys), (b, 1, h, d), bf)

    def decode(impl_):
        return lambda q, k, v, **kw: pd.flash_decode(
            q, k, v, idx, impl=impl_, **kw)

    for name, nkv in (("dense", h), (f"GQA {h}/{hkv}", hkv)):
        k, v = (_rand(next(keys), (b, r, nkv, d), bf) for _ in range(2))
        cases.append((f"flash_decode {name}", decode(impl), decode("xla"),
                      (qd, k, v), TOL_BF16))
    # windowed ring + sinks: cursors far past the ring's length
    ring_w = r - size.sinks
    ring_cursors = [int(c) * 3 + 5 for c in cursors]
    ring_cursors[0] = r // 2  # one slot whose ring is not full yet
    sp = jnp.asarray(_ring_slot_pos(ring_cursors, r, size.sinks, ring_w))
    ridx = jnp.asarray(ring_cursors, jnp.int32)
    k, v = (_rand(next(keys), (b, r, h, d), bf) for _ in range(2))

    def ring(impl_):
        return lambda q, k, v, sp: pd.flash_decode(
            q, k, v, ridx, slot_pos=sp, window=ring_w, sinks=size.sinks,
            impl=impl_)

    cases.append(("flash_decode ring+sinks", ring(impl), ring("xla"),
                  (qd, k, v, sp), TOL_BF16))
    # int8 cache with per-row-per-head f32 scales, dequantised in-kernel
    k8, v8 = (jax.random.randint(next(keys), (b, r, h, d), -127, 128,
                                 jnp.int8) for _ in range(2))
    ks, vs = (jax.random.uniform(next(keys), (b, r, h), jnp.float32,
                                 0.005, 0.02) for _ in range(2))

    def int8(impl_):
        return lambda q, k, v, ks, vs: pd.flash_decode(
            q, k, v, idx, k_scale=ks, v_scale=vs, impl=impl_)

    cases.append(("flash_decode int8", int8(impl), int8("xla"),
                  (qd, k8, v8, ks, vs), TOL_BF16))
    # paged pool: a shuffled page table, the tail of each slot unbound
    nb, bs = size.pool_blocks, size.pool_block_rows
    pages = r // bs
    perm = np.random.default_rng(0).permutation(nb)[:b * pages]
    table = perm.reshape(b, pages).astype(np.int32)
    for row, c in enumerate(cursors):
        table[row, c // bs + 1:] = -1
    table = jnp.asarray(table)
    kp, vp = (_rand(next(keys), (nb, bs, h, d), bf) for _ in range(2))

    def paged(impl_):
        return lambda q, k, v, pt: pd.flash_decode_paged(
            q, k, v, pt, idx, impl=impl_)

    cases.append(("flash_decode_paged", paged(impl), paged("xla"),
                  (qd, kp, vp, table), TOL_BF16))

    # fused Adam over one flat ResNet-50-sized f32 buffer
    n = size.adam_elems + (-size.adam_elems) % 1024
    p, g = (_rand(next(keys), (n,), jnp.float32) for _ in range(2))
    m = 0.1 * _rand(next(keys), (n,), jnp.float32)
    vv = jnp.abs(_rand(next(keys), (n,), jnp.float32)) * 0.01

    def adam(impl_):
        return lambda p, g, m, v: zf.fused_adam_update(
            p, g, m, v, jnp.int32(3), lr=1e-3, impl=impl_)

    cases.append((f"fused_adam_update n={n}", adam(impl), adam("xla"),
                  (p, g, m, vv), TOL_F32))
    return cases


def run_kernels(size: Size, device: dict) -> None:
    """Every Pallas entry point: compiled on this device, the compiled
    program holds the kernel (not its XLA stand-in), and the result
    agrees with the XLA reference."""
    import jax
    import numpy as np

    on_tpu = size.platform == "tpu"
    # off the chip (the CPU rehearsal) the same kernels run interpreted
    impl = "pallas" if on_tpu else "interpret"
    for name, fn, ref, args, tol in _kernel_cases(size, impl):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        secs = time.perf_counter() - t0
        if on_tpu:
            assert "tpu_custom_call" in compiled.as_text(), (
                f"{name}: compiled program holds no Pallas kernel")
        got = jax.tree.leaves(compiled(*args))
        want = jax.tree.leaves(jax.jit(ref)(*args))
        assert len(got) == len(want)
        worst = 0.0
        for a, b in zip(got, want):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            assert a.shape == b.shape, (name, a.shape, b.shape)
            assert np.isfinite(a).all(), f"{name}: non-finite output"
            err = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6))
            worst = max(worst, err)
        assert worst <= tol, f"{name}: error {worst:.3g} > {tol:g}"
        log(f"[kernels] {name}: compiled in {secs:.1f}s on "
            f"{device['kind']}, max error {worst:.2e} (tol {tol:g})")


# ---------------------------------------------------------------------------
# phase: cache
# ---------------------------------------------------------------------------


def run_cache() -> None:
    from fluxdistributed_tpu import compilation

    cm = compilation.compile_metrics()
    log(f"[cache] directory {compilation.persistent_cache_dir()}: "
        f"{cm['cache_hits']} hits, {cm['cache_misses']} misses, "
        f"{cm['compile_seconds']}s compiling "
        f"({cm['compile_seconds_saved']}s saved by hits)")


# ---------------------------------------------------------------------------
# --chips 4: data parallel over four chips vs one
# ---------------------------------------------------------------------------

# How close the four-chip runs must come to the one-chip run on the same
# global batch (tests/test_dp_invariants.py's invariant, at full width).
# Both compute the global-batch mean gradient with global BatchNorm
# statistics (GSPMD reduces them itself; the shard_map step gets them
# from bn_cross_replica_axis), so they differ only in bf16 rounding and
# in the order f32 sums are taken across four shards.  Loss is ~6.9:
# 5e-2 is under 1%.  The update  p_final - p_init  is what a wrong
# gradient reduction changes (a missing mean is 4x, a missing reduction
# is a different direction), so it is held to 10% in relative L2 norm,
# which bf16 noise amplified through 5 steps of BatchNorm stays well
# inside; the parameters themselves then agree to 1e-3 relative.
DP_LOSS_ATOL = 5e-2
DP_UPDATE_REL = 1e-1
DP_PARAM_REL = 1e-3


def _dp_run(size: Size, devs, spmd: str):
    """``size.dp_steps`` steps of one repeated global batch through the
    trainer's compiled step on a mesh over ``devs``."""
    import jax
    import numpy as np

    from fluxdistributed_tpu import mesh as mesh_lib
    from fluxdistributed_tpu import models, optim
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.train import prepare_training

    mesh = mesh_lib.data_mesh(devs=devs)
    dataset = SyntheticDataset(
        nsamples=size.dp_batch, nclasses=size.classes,
        shape=(size.image, size.image, 3), seed=0)
    kw = {}
    if spmd == "shard_map":
        # per-shard BatchNorm statistics would be a different model;
        # the explicit-SPMD step syncs them over the data axis
        kw["bn_cross_replica_axis"] = mesh_lib.DATA_AXIS
    model = getattr(models, size.dp_model)(num_classes=size.classes, **kw)
    task = prepare_training(
        model, dataset, optim.momentum(0.01, 0.9), mesh=mesh,
        batch_size=size.dp_batch, cycles=size.dp_steps, seed=0, spmd=spmd)
    batch = next(iter(task.loader))
    init = jax.tree.map(np.asarray, task.state.params)
    placed = {s.device for s in batch["image"].addressable_shards}
    assert placed == set(devs), (placed, devs)
    hlo = task.step_fn.lower(task.state, batch).compile().as_text()
    state, losses = task.state, []
    for _ in range(size.dp_steps):
        state, m = task.step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert int(state.step) == size.dp_steps
    for leaf in jax.tree.leaves(state.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == len(devs)
        for c in copies[1:]:  # replicas bit-identical across devices
            assert np.array_equal(copies[0], c)
    out = {
        "losses": losses,
        "init": init,
        "params": jax.tree.map(np.asarray, state.params),
        "image": np.asarray(batch["image"]),
        "label": np.asarray(batch["label"]),
        "hlo": hlo,
    }
    del task, state, batch
    gc.collect()
    return out


def _tree_norm(tree) -> float:
    import jax
    import numpy as np

    return math.sqrt(sum(float(np.sum(np.square(x, dtype=np.float64)))
                         for x in jax.tree.leaves(tree)))


def run_dp4(size: Size, device: dict) -> None:
    import jax
    import numpy as np

    devs = jax.devices()
    assert len(devs) >= 4, len(devs)
    devs = devs[:4]
    one = _dp_run(size, devs[:1], "jit")
    log(f"[dp4] one device: losses {one['losses']}")
    assert all(math.isfinite(l) for l in one["losses"])
    update_one = jax.tree.map(np.subtract, one["params"], one["init"])
    for spmd in ("jit", "shard_map"):
        four = _dp_run(size, devs, spmd)
        log(f"[dp4] four devices, spmd={spmd}: losses {four['losses']}")
        # the same seed gave the same global batch and the same weights
        assert np.array_equal(four["label"], one["label"])
        assert np.array_equal(four["image"], one["image"])
        assert _tree_norm(jax.tree.map(
            np.subtract, four["init"], one["init"])) == 0.0
        assert "all-reduce" in four["hlo"], (
            f"spmd={spmd}: the four-chip step holds no all-reduce")
        np.testing.assert_allclose(
            four["losses"], one["losses"], rtol=0, atol=DP_LOSS_ATOL)
        update = jax.tree.map(np.subtract, four["params"], four["init"])
        rel_u = _tree_norm(jax.tree.map(
            np.subtract, update, update_one)) / _tree_norm(update_one)
        rel_p = _tree_norm(jax.tree.map(
            np.subtract, four["params"], one["params"])) / _tree_norm(
                one["params"])
        log(f"[dp4] spmd={spmd} vs one device on {device['kind']}: max "
            f"|loss diff| "
            f"{np.max(np.abs(np.subtract(four['losses'], one['losses']))):.2e}"
            f" (tol {DP_LOSS_ATOL:g}), update rel L2 {rel_u:.2e} "
            f"(tol {DP_UPDATE_REL:g}), params rel L2 {rel_p:.2e} "
            f"(tol {DP_PARAM_REL:g})")
        assert rel_u <= DP_UPDATE_REL, rel_u
        assert rel_p <= DP_PARAM_REL, rel_p


# ---------------------------------------------------------------------------


def main(argv=None, *, size: Size = FULL) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the four-chip data-parallel path "
                         "and its one-device comparison")
    args = ap.parse_args(argv)

    device = require_device(size.platform, args.chips)
    log(f"[device] {device}")

    from fluxdistributed_tpu import compilation

    log(f"[cache] compile cache at {compilation.enable_persistent_cache()}")
    if args.chips == 4:
        run_dp4(size, device)
    else:
        run_train(size, device)
        gc.collect()
        run_kernels(size, device)
        run_cache()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
