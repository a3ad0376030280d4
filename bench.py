"""Benchmark harness: ResNet-50/ImageNet train-step throughput.

The reference publishes NO benchmark numbers (SURVEY §6, BASELINE.md) —
its only timing hook is dead code.  This harness therefore defines the
baseline: steady-state images/sec/chip for the full compiled DP training
step (forward + backward + grad all-reduce + optimizer update, bf16
compute) on synthetic 224x224 data, the reference's headline workload
(ResNet-50/ImageNet, README.md:27,43).

One process, which touches the chip once.  It measures on a TPU only: no
TPU, an unknown ``device_kind`` or a failed measurement is a NON-ZERO
exit (with an error JSON line saying where it died), never a CPU timing
under the same metric name.  On success it prints ONE JSON line and
exits 0:
  {"metric": ..., "value": N, "unit": "images/sec/chip",
   "platform": "tpu", "device_kind": "...", "device_count": N, ...}
"""

from __future__ import annotations

import json
import sys
import time
import traceback

# An early builder's figure (global batch 256, bf16, f32 input feed) that
# ``vs_baseline`` divides by.  It never appeared in the driver's record
# and is not measured on today's code: the first measured cell of the
# benchmark PR replaces it.
BASELINE_IMAGES_PER_SEC_PER_CHIP = 2270.0


def time_compiled_step(step, state, b, target_seconds: float = 2.0,
                       on_compiled=None):
    """Shared measurement protocol: compile + 3-step warmup (the first
    post-compile steps can still hit allocator warm-up and skew short
    timings), then an adaptive timed loop covering ``target_seconds``.
    Returns ``(seconds_per_step, iters)``.  benchmarks/step_sweep.py uses
    this same helper so sweep rows stay comparable to the headline.
    ``on_compiled`` fires once the first step has landed (compilation
    over) — the bench's phase marker for timeout forensics."""
    import time as _time

    import jax

    state, m = step(state, b)
    jax.block_until_ready(m["loss"])
    if on_compiled is not None:
        on_compiled()
    t0 = _time.perf_counter()
    for _ in range(3):
        state, m = step(state, b)
    jax.block_until_ready(m["loss"])
    warm = (_time.perf_counter() - t0) / 3

    iters = max(5, int(target_seconds / max(warm, 1e-3)))
    t0 = _time.perf_counter()
    for _ in range(iters):
        state, m = step(state, b)
    jax.block_until_ready(m["loss"])
    return (_time.perf_counter() - t0) / iters, iters


def fuse_steps(step, k: int, donate: bool = True):
    """Wrap a compiled ``step(state, batch) -> (state, metrics)`` into ONE
    program running ``k`` optimizer steps on the same device-resident
    batch.  Isolates host-side dispatch cost: each un-fused step pays
    one host dispatch; ``k`` fused steps pay one between them.
    Semantics differ from real training only in reusing the batch."""
    import jax

    def multi(state, b):
        def body(_, carry):
            st, _m = carry
            return step(st, b)

        # one step seeds the (state, metrics) carry; k-1 more in the loop
        return jax.lax.fori_loop(0, k - 1, body, step(state, b))

    return jax.jit(multi, donate_argnums=(0,) if donate else ())


def build_step(
    batch: int,
    size: int = 224,
    donate: bool = True,
    accum_steps: int = 1,
    norm_dtype=None,
    input_f32: bool = False,
    remat: bool = False,
    fuse: int = 1,
    s2d: bool = False,
    zero1: bool = False,
    layout: str | None = None,
):
    """Build the headline measurement target: ResNet-50, DP mesh over all
    chips, compiled train step, device-resident batch.

    Returns ``(step, state, batch_dict)``.  This is THE protocol —
    benchmarks/step_sweep.py varies its knobs through here so sweep rows
    stay comparable to the headline number.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import fluxdistributed_tpu as fd
    from fluxdistributed_tpu import optim, sharding
    from fluxdistributed_tpu.models import resnet50
    from fluxdistributed_tpu.parallel import TrainState, make_train_step
    from fluxdistributed_tpu.parallel.dp import flax_loss_fn

    lay = None
    if layout:
        # rule-derived dp x fsdp x tp placement (parallel/layout.py):
        # the mesh and the state shardings come from the preset's rule
        # table + fsdp overlay — sweep rows measure the SAME step math
        # under a different placement
        from fluxdistributed_tpu.parallel import layout as layout_lib

        if zero1:
            raise ValueError("layout= and zero1= are exclusive (a "
                             "layout's fsdp axis shards the optimizer)")
        lay = layout_lib.resolve_layout(layout)
        mesh = lay.build_mesh()
    else:
        mesh = fd.data_mesh()
    model = resnet50(
        num_classes=1000, norm_dtype=norm_dtype, remat=remat,
        space_to_depth=s2d,
    )
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, (batch, size, size, 3)).astype(np.float32)
    if s2d:
        # host-side re-layout, like a real input pipeline would feed it
        from fluxdistributed_tpu.models.resnet import space_to_depth

        x = np.ascontiguousarray(space_to_depth(x))
    y = rng.integers(0, 1000, batch)

    variables = model.init(jax.random.PRNGKey(0), x[:1], train=True)
    params = variables["params"]
    mstate = {k: v for k, v in variables.items() if k != "params"}

    loss_fn = flax_loss_fn(model, fd.logitcrossentropy)
    opt = optim.momentum(0.1, 0.9)
    if zero1:
        # ZeRO-1 weight-update sharding: same step math, optimizer state
        # + update compute sharded 1/N over the data axis
        from fluxdistributed_tpu.parallel import zero1 as zero1_lib

        state, z_sh = zero1_lib.zero1_state(
            params, opt, mesh, model_state=sharding.replicate(mstate, mesh)
        )
        step = zero1_lib.make_train_step_zero1(
            loss_fn, opt, mesh, z_sh, donate=donate, accum_steps=accum_steps
        )
    elif lay is not None:
        from fluxdistributed_tpu.parallel import layout as layout_lib

        state = TrainState.create(params, opt, model_state=mstate)
        spec_state = layout_lib.state_specs_for(
            model, state, lay, mesh)
        sh = sharding.make_shardings(spec_state, mesh)
        state = jax.tree.map(
            lambda v, s: jax.device_put(sharding.unaliased(v), s),
            state, sh)
        step = make_train_step(
            loss_fn, opt, mesh, axis=lay.batch_axes, donate=donate,
            accum_steps=accum_steps, state_shardings=sh)
    else:
        step = make_train_step(loss_fn, opt, mesh, donate=donate, accum_steps=accum_steps)
        state = TrainState.create(
            sharding.replicate(params, mesh), opt, model_state=sharding.replicate(mstate, mesh)
        )
    # feed bf16 by default: the model casts to bf16 at its input anyway,
    # so an f32 feed only adds a 2x-wider HBM read + an in-graph convert
    xb = x if input_f32 else x.astype(jnp.bfloat16)
    b = sharding.shard_batch(
        {"image": xb, "label": np.asarray(fd.onehot(y, 1000))}, mesh,
        axis=(lay.batch_axes if lay is not None else "data"),
    )
    if fuse > 1:
        step = fuse_steps(step, fuse, donate=donate)
    return step, state, b


# bf16 peak TFLOP/s per chip, for the MFU denominator, keyed by jax's
# exact ``device_kind``.  Sources: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v5e": 197 TFLOP/s
# bf16 per chip).  A device that is not here is an error, not a default.
_PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
}


def device_info() -> dict:
    """The device as jax reports it — rides every result line."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def require_tpu(info: dict) -> None:
    """Fail up front unless the platform is a TPU whose peak is known:
    a measurement path that finds no chip fails, it does not time the
    CPU under a device metric's name."""
    if info["platform"] != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU; jax found platform "
            f"{info['platform']!r} ({info['device_kind']})")
    peak_bf16_tflops(info["device_kind"])


def peak_bf16_tflops(device_kind: str) -> float:
    try:
        return _PEAK_BF16_TFLOPS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no bf16 peak recorded for device_kind {device_kind!r}: add "
            "it to _PEAK_BF16_TFLOPS with its source") from None


def step_flops(step, state, b) -> float:
    """Total FLOPs of one step from XLA's HLO cost analysis on the
    LOWERED (pre-compile) program — no second backend compile.  0.0
    when the analysis is unavailable."""
    try:
        ca = step.lower(state, b).cost_analysis()
        d = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(d.get("flops", 0.0)) if d else 0.0
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return 0.0


def mfu_pct(flops: float, dt: float, nchips: int):
    """Model-FLOPs-utilization of a measured step: achieved FLOP/s per
    chip over the chip's bf16 peak.  None when XLA reports no FLOP
    count; an unknown ``device_kind`` raises."""
    import jax

    peak = peak_bf16_tflops(jax.devices()[0].device_kind)
    if not flops:
        return None
    return round(flops / dt / nchips / (peak * 1e12) * 100, 2)


def guard_stamp():
    """The robustness-counter stamp for the bench JSON: every
    ``fdtpu_guard_* / fdtpu_fault_* / fdtpu_watchdog_*`` series (plus
    the OOM-skip counter) snapshotted from the process registry.  A
    dead hardware round's artifact then records WHY it died — faults
    injected/retried/given up, stalls and escalations, anomalies
    quarantined — instead of a bare ``value: 0``.  Like
    :func:`lint_stamp`, it never raises and rides success and error
    JSON alike."""
    try:
        from fluxdistributed_tpu.obs import get_registry

        snap = get_registry().snapshot()
        keep = ("fdtpu_guard_", "fdtpu_fault_", "fdtpu_watchdog_",
                "fdtpu_train_oom_skipped_total")
        out = {k: v for k, v in snap.items()
               if k.startswith(keep) and v}
        return out or {"clean": True}
    except Exception as e:  # noqa: BLE001 — stamp is best-effort
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def memory_stamp(state=None):
    """The HBM stamp for the bench JSON: live per-device memory truth
    (``device.memory_stats()`` through obs.memstats — bytes in use,
    PEAK since process start, limit and the min headroom ratio) plus,
    when the bench state is at hand, its exact static bytes (params /
    optimizer state off the leaf shapes).  On CPU it reads
    ``{"available": false}`` — unavailable, never fake zeros.  Like the
    lint/guard stamps it never raises and rides success AND error JSON,
    so a failed run records the memory state at death — e.g. "we were
    at 2% headroom when it OOMed"."""
    try:
        from fluxdistributed_tpu.obs import memstats

        out = memstats.hbm_summary()
        if state is not None:
            out["static"] = memstats.state_bytes(state)
        return out
    except Exception as e:  # noqa: BLE001 — stamp is best-effort
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def lint_stamp():
    """The static-health stamp for the bench JSON: the AST-layer
    (FDT1xx) + concurrency-layer (FDT3xx) rule-count summary, a
    per-layer ``"layers"`` breakdown, and the new-vs-baseline count
    from the fdtpu-lint suite (seconds of pure host-side parsing, no
    jax tracing).  A run whose artifact says ``"new": 0`` provably ran
    code the analyzer had no fresh complaints about — including no
    unlocked shared-state writes or lock-order cycles; a non-zero count
    flags the run as statically suspect before anyone spends chip time
    reproducing it.  Never raises — forensics must not kill the
    bench."""
    try:
        from fluxdistributed_tpu import analysis

        return analysis.lint_verdict()
    except Exception as e:  # noqa: BLE001 — stamp is best-effort
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def pp_plan_stamp():
    """The pipeline-planner paired-row stamp for the bench JSON: the
    profile-guided planner (parallel/pp_plan.py) run on THIS box's
    static costs for a production-shaped LM (lm_small geometry, 32k
    vocab) — uniform vs planned stage boundaries with the modeled
    bubble of each.  Staging only, nothing compiles, and like the
    lint/guard stamps it never raises: every round's artifact records
    whether (and by how much) planner placement beats uniform splits
    here, next to the measured rows of benchmarks/pp_bubble.py."""
    try:
        from fluxdistributed_tpu.models.transformer_lm import lm_small
        from fluxdistributed_tpu.parallel.pp_plan import plan_from_model

        S, M = 4, 16
        model = lm_small(dropout=0.0)
        plan = plan_from_model(model, S, M, batch_size=8, seqlen=1024)
        return {
            "S": S, "M": M, "depth": int(model.depth),
            "boundaries_planned": list(plan.boundaries),
            "counts_planned": list(plan.counts),
            "modeled_bubble_planned": round(plan.modeled_bubble, 4),
            "modeled_bubble_uniform": round(plan.uniform_bubble, 4),
        }
    except Exception as e:  # noqa: BLE001 — stamp is best-effort
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def layout_pick_stamp():
    """The auto-layout picker's verdict for the bench JSON
    (parallel/layout.py): chosen dp x fsdp x tp layout for the bench-
    shaped LM on THIS topology, with each candidate's peak bytes /
    headroom and collective-ledger figures.  Budget comes from the live
    per-device ``bytes_limit`` when the backend reports one (real
    chips); without it (CPU) the ranking is by collective bytes alone,
    honestly flagged.  Prices candidates by ABSTRACT compiles (no
    parameter buffer allocates) — bounded cost, and like the lint/
    guard/memory stamps it never raises: dead rounds record what the
    picker would have chosen next to why the round died."""
    try:
        import jax
        import numpy as np

        from fluxdistributed_tpu import optim
        from fluxdistributed_tpu.models.transformer_lm import lm_tiny
        from fluxdistributed_tpu.parallel import layout as layout_lib

        model = lm_tiny(dropout=0.0)
        batch = {"tokens": jax.ShapeDtypeStruct((16, 128), np.int32)}
        rep = layout_lib.pick(model, batch, optim.adam(1e-3))
        rows = [{k: r.get(k) for k in (
                    "layout", "peak_bytes", "headroom_bytes", "fits",
                    "comms_bytes", "comms_bytes_per_axis", "invalid")
                 if r.get(k) is not None}
                for r in rep.rows]
        return {"chosen": rep.chosen.name if rep.chosen else None,
                "chosen_sizes": rep.chosen.sizes if rep.chosen else None,
                "budget_bytes": rep.budget_bytes,
                "reason": rep.reason,
                "rows": rows}
    except Exception as e:  # noqa: BLE001 — stamp is best-effort
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def default_runs_ledger():
    """Resolve the cross-run ledger path for bench runs:
    ``FDTPU_RUNS_LEDGER`` when set (empty string disables), else
    ``benchmarks/hw/runs.jsonl`` next to this file — the history
    ``bin/trends.py`` renders trends from and gates regressions
    against."""
    import os

    env = os.environ.get("FDTPU_RUNS_LEDGER")
    if env is not None:
        return env or None
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "hw", "runs.jsonl")


def append_run_record(out, kind="bench", fingerprint=None):
    """Mirror one bench JSON (success AND error alike) into the
    cross-run ledger (obs.runs).  Best-effort by contract: the ledger
    append must never change what the bench prints or returns."""
    try:
        path = default_runs_ledger()
        if not path:
            return
        from fluxdistributed_tpu.obs import runs as runs_lib

        metrics = {}
        if out.get("value"):
            metrics["throughput"] = out["value"]
        if out.get("mfu_pct") is not None:
            metrics["mfu_pct"] = out["mfu_pct"]
        if out.get("compile_seconds"):
            metrics["compile_seconds"] = out["compile_seconds"]
        stamps = {k: out[k] for k in
                  ("lint", "guard", "memory", "layout_pick", "pp_plan")
                  if k in out}
        extra = {k: out[k] for k in
                 ("unit", "platform", "device_kind", "device_count",
                  "cache_hits", "cache_misses")
                 if k in out}
        runs_lib.append_run(path, runs_lib.run_record(
            kind,
            fingerprint=fingerprint,
            phase=out.get("phase"),
            retryable=out.get("retryable"),
            error=out.get("error"),
            metrics=metrics,
            stamps=stamps or None,
            **extra))
    except Exception:  # noqa: BLE001 — the ledger is forensics
        pass


def _unavailable_sigs():
    """The canonical backend-unavailable signature list lives in
    ``fluxdistributed_tpu.faults`` (one source, no drift); a frozen
    fallback keeps the error-JSON path alive even when the package
    itself cannot import (that is precisely an error path)."""
    try:
        from fluxdistributed_tpu.faults import UNAVAILABLE_SIGNATURES

        return UNAVAILABLE_SIGNATURES
    except Exception:  # noqa: BLE001 — classification must never crash
        return ("UNAVAILABLE", "DEADLINE_EXCEEDED", "failed to connect",
                "Connection reset", "Connection refused", "Socket closed",
                "response body closed", "remote_compile",
                "No visible device", "Unable to initialize backend",
                "timed out", "per-attempt bound")


def retryable_error(phase: str, err: str) -> bool:
    """Phase-aware transient/permanent classification for bench error
    JSON: whoever re-runs a failed bench should do so ONLY when this
    says True — a real code failure needs a fix, not another attempt.

    * ``backend_init`` — always retryable: the backend failed to
      initialise (no chip, or one held by another process);
    * everything else (``build`` / ``compile`` / ``measure``) —
      retryable only when the error carries a backend-unavailable
      signature (runtime eviction, a timeout).  A deterministic
      Python/XLA error in any phase — including compile — is
      permanent: retrying a broken build never succeeds.
    """
    if phase == "backend_init":
        return True
    err = err or ""
    return any(sig in err for sig in _unavailable_sigs())


def _measure(progress: dict) -> dict:
    """The measurement.  ``progress["phase"]`` names where it stands, so
    the error line of a failed run says where it died."""
    from fluxdistributed_tpu import compilation
    from fluxdistributed_tpu.obs import jaxmon

    jaxmon.install()  # compile/cache counters from the first compile on
    progress["phase"] = "backend_init"
    cache_dir = compilation.enable_persistent_cache()
    dev = device_info()
    # every line, an error line too, names the device and the cache
    progress.update(dev, compile_cache_dir=cache_dir)
    require_tpu(dev)

    nchips = dev["device_count"]
    # 256/chip: the paper's workload, and ResNet-50's activations fit
    # the 16 GB of a v5e with room to spare
    batch = 256 * nchips

    progress["phase"] = "build"
    step, state, b = build_step(batch)
    # FLOP count before the timed loop: the donated state's buffers are
    # gone after the first step call, and lower() is a cheap local trace
    fl = step_flops(step, state, b)
    progress["phase"] = "compile"
    dt, _ = time_compiled_step(
        step, state, b,
        on_compiled=lambda: progress.update(phase="measure"))
    cm = compilation.compile_metrics()
    progress["phase"] = "done"

    ips_per_chip = batch / dt / nchips
    return {
        "metric": "ResNet-50 train-step throughput "
                  f"({dev['platform']}, global batch {batch}, bf16)",
        "value": round(ips_per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            ips_per_chip / BASELINE_IMAGES_PER_SEC_PER_CHIP, 3),
        **dev,
        "mfu_pct": mfu_pct(fl, dt, nchips),
        # cold-start ledger: where the wall time ahead of the timed loop
        # went, and how much of it the persistent cache absorbed
        "compile_seconds": cm["compile_seconds"],
        "cache_hits": cm["cache_hits"],
        "cache_misses": cm["cache_misses"],
        "compile_seconds_saved": cm["compile_seconds_saved"],
        "compile_cache_dir": cache_dir,
        # static-health stamp: the lint verdict this code measured under
        "lint": lint_stamp(),
        # robustness forensics: fault/watchdog/guard counters this
        # measurement accumulated (retries survived, stalls seen)
        "guard": guard_stamp(),
        # HBM forensics: static state bytes + live per-device memory
        # (peak included) when memory_stats() is live on this backend
        "memory": memory_stamp(state),
        # planner paired row: uniform vs planned modeled bubble for a
        # production-shaped LM on this box's static costs
        "pp_plan": pp_plan_stamp(),
        # auto-layout picker verdict: chosen dp x fsdp x tp layout for
        # the bench-shaped LM on THIS topology, with each candidate's
        # headroom + collective-ledger figures (parallel/layout.py)
        "layout_pick": layout_pick_stamp(),
    }


def main() -> int:
    """One process, one measurement: 0 and a result line, or 1 and an
    error line that carries no value under the metric's name."""
    progress = {"phase": "backend_init"}
    try:
        out = _measure(progress)
    except Exception as e:  # noqa: BLE001 — reported, then a non-zero exit
        traceback.print_exc(file=sys.stderr)
        from fluxdistributed_tpu import compilation

        err = f"{type(e).__name__}: {e}"
        out = {
            "metric": "ResNet-50 train-step throughput",
            "error": err[:500],
            **progress,
            "retryable": retryable_error(progress["phase"], err),
            **{k: v for k, v in compilation.compile_metrics().items()
               if k in ("compile_seconds", "cache_hits", "cache_misses")},
            "lint": lint_stamp(),
            "guard": guard_stamp(),
            "memory": memory_stamp(),
        }
        print(json.dumps(out))
        append_run_record(out)
        return 1
    print(json.dumps(out))
    append_run_record(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
