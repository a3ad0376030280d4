#!/usr/bin/env python
"""Train-step configuration sweep for the ResNet-50 bench.

Measures steady-state img/s for combinations of model/input dtype
variants and XLA flags.  XLA flags bind at backend init, so the parent
re-execs itself (``--one``) with each configuration's environment and
collects one JSON line per child.

One process per chip: the parent imports nothing but the standard
library — never jax, never ``bench`` (``measure_one`` imports both, and
only a ``--one`` child calls it) — so it cannot initialise a backend or
hold the chip.  Children run strictly one after another, each alone on
the chip for its whole life.

Run on the real chip:  python benchmarks/step_sweep.py
Child mode (internal): python benchmarks/step_sweep.py --one
(configuration reaches the child via SWEEP_* environment variables)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# bench.py (the shared timing protocol) lives at the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Ordered by expected leverage: if chip time runs out mid-sweep, the
# rows most likely to move the headline number have already printed.
CONFIGS = [
    {"name": "baseline-bf16", "env": {}},
    # fused multi-step: K optimizer steps per dispatch — if throughput
    # jumps with fusion, the gap is host dispatch latency, not on-chip
    # time
    {"name": "fuse-8", "env": {"SWEEP_FUSE": "8"}},
    {"name": "fuse-32", "env": {"SWEEP_FUSE": "32"}},
    # MXU-shaped stem: space_to_depth input + equivalent 4x4/1 conv
    # replaces the 7x7/2-on-3-channels stem pathology (exact re-layout,
    # tests/test_resnet_s2d.py)
    {"name": "s2d-stem", "env": {"SWEEP_S2D": "1"}},
    # combined best-case candidates: stem fix x batch x fused dispatch
    {"name": "s2d-512", "env": {"SWEEP_S2D": "1", "SWEEP_BATCH": "512"}},
    {"name": "s2d-fuse-8", "env": {"SWEEP_S2D": "1", "SWEEP_FUSE": "8"}},
    {"name": "latency-hiding-sched", "env": {
        "SWEEP_XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true"}},
    # full lever stack: if individual levers help, their combination is
    # the real headline candidate
    {"name": "s2d-lhs-512", "env": {
        "SWEEP_S2D": "1", "SWEEP_BATCH": "512",
        "SWEEP_XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true"}},
    {"name": "s2d-lhs-fuse-8", "env": {
        "SWEEP_S2D": "1", "SWEEP_FUSE": "8",
        "SWEEP_XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true"}},
    # ZeRO-1 weight-update sharding: optimizer state + update 1/N over
    # the data axis (reduce-scatter grads, all-gather params).  The
    # momentum update is cheap vs ResNet-50 FLOPs, so this measures the
    # reduce-scatter+all-gather vs all-reduce trade at DP numerics
    {"name": "zero1", "env": {"SWEEP_ZERO1": "1"}},
    {"name": "zero1-512", "env": {"SWEEP_ZERO1": "1", "SWEEP_BATCH": "512"}},
    # rule-derived dp x fsdp layouts (parallel/layout.py): the SAME dp
    # step math under ZeRO-3-style placement from the declarative rule
    # tables — measures the all-gather/reduce-scatter trade the layout
    # picker's ledger models, on the real chip
    {"name": "layout-fsdp", "env": {"SWEEP_LAYOUT": "fsdp"}},
    {"name": "layout-dp-fsdp-512", "env": {
        "SWEEP_LAYOUT": "dp_fsdp", "SWEEP_BATCH": "512"}},
    {"name": "batch-512", "env": {"SWEEP_BATCH": "512"}},
    {"name": "lhs-batch-512", "env": {
        "SWEEP_BATCH": "512",
        "SWEEP_XLA_FLAGS": "--xla_tpu_enable_latency_hiding_scheduler=true"}},
    # remat trades ~1 extra forward for O(depth)x less activation memory;
    # worth it iff the bigger batch it unlocks beats the FLOPs cost
    {"name": "remat-1024", "env": {"SWEEP_REMAT": "1", "SWEEP_BATCH": "1024"}},
    {"name": "remat-512", "env": {"SWEEP_REMAT": "1", "SWEEP_BATCH": "512"}},
    {"name": "bn-f32", "env": {"SWEEP_BN_F32": "1"}},
    {"name": "input-f32", "env": {"SWEEP_INPUT_F32": "1"}},
    {"name": "no-donate", "env": {"SWEEP_NO_DONATE": "1"}},
    {"name": "grad-accum-2", "env": {"SWEEP_ACCUM": "2", "SWEEP_BATCH": "512"}},
]


def _env_flag(name: str) -> bool:
    """'1'/'true'/'yes' enable, ''/'0'/'false'/'no'/unset disable."""
    return os.environ.get(name, "").strip().lower() in ("1", "true", "yes", "on")


def measure_one() -> dict:
    import jax

    if os.environ.get("SWEEP_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["SWEEP_PLATFORM"])
    import jax.numpy as jnp

    import bench

    batch = int(os.environ.get("SWEEP_BATCH", "256"))
    fuse = int(os.environ.get("SWEEP_FUSE", "1"))
    step, state, b = bench.build_step(
        batch,
        size=int(os.environ.get("SWEEP_SIZE", "224")),
        donate=not _env_flag("SWEEP_NO_DONATE"),
        accum_steps=int(os.environ.get("SWEEP_ACCUM", "1")),
        norm_dtype=jnp.float32 if _env_flag("SWEEP_BN_F32") else None,
        input_f32=_env_flag("SWEEP_INPUT_F32"),
        remat=_env_flag("SWEEP_REMAT"),
        fuse=fuse,
        s2d=_env_flag("SWEEP_S2D"),
        zero1=_env_flag("SWEEP_ZERO1"),
        layout=os.environ.get("SWEEP_LAYOUT") or None,
    )
    dt, _ = bench.time_compiled_step(
        step, state, b, target_seconds=float(os.environ.get("SWEEP_SECONDS", "2.0"))
    )
    # one fused call covers `fuse` optimizer steps on the same batch
    return {
        "img_per_sec_per_chip": round(batch * fuse / dt / jax.device_count(), 1),
        "step_ms": round(dt * 1e3 / fuse, 2),
        "batch": batch,
        "fuse": fuse,
        "platform": jax.devices()[0].platform,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", action="store_true",
                    help="child mode: measure the SWEEP_* env configuration")
    ap.add_argument("--platform", default=None,
                    help="force platform for every child (e.g. cpu for a "
                         "smoke run on the fake-device mesh)")
    args = ap.parse_args()
    if args.platform:
        os.environ["SWEEP_PLATFORM"] = args.platform
    if args.one:
        print(json.dumps(measure_one()))
        return

    # between children is the only place to stop without killing a
    # process that holds the chip, so the parent checks the deadline
    # here and skips what no longer fits a child's 1800 s self-bound
    deadline = int(os.environ.get("SWEEP_DEADLINE_EPOCH", "0") or 0)
    results = []
    for cfg in CONFIGS:
        if deadline and time.time() + 1800 > deadline:
            print(json.dumps({"config": cfg["name"],
                              "error": "skipped: deadline"}), flush=True)
            continue
        env = {**os.environ, **cfg["env"]}
        # APPEND sweep flags to pre-existing XLA_FLAGS so the row stays
        # comparable to the others (which inherit the environment's flags)
        extra = env.pop("SWEEP_XLA_FLAGS", None)
        if extra:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + extra).strip()
        try:
            # generous timeout — killing a child that holds the chip is
            # a last resort, not a scheduling tool
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one"],
                env=env, capture_output=True, text=True, timeout=1800,
            )
        except subprocess.TimeoutExpired as e:
            # TimeoutExpired.stderr is bytes even under text=True
            err = e.stderr or b""
            if isinstance(err, bytes):
                err = err.decode(errors="replace")
            results.append({"config": cfg["name"], "error": "timeout",
                            "stderr": err[-300:]})
            print(json.dumps(results[-1]), flush=True)
            continue
        lines = p.stdout.strip().splitlines()
        r = None
        if lines:
            try:
                r = json.loads(lines[-1])
            except json.JSONDecodeError:
                pass
        if r is None or p.returncode != 0:
            r = {"error": f"rc={p.returncode}",
                 "stderr": p.stderr.strip()[-300:], **(r or {})}
        results.append({"config": cfg["name"], **r})
        print(json.dumps(results[-1]), flush=True)
    print(json.dumps({"sweep": results}))


if __name__ == "__main__":
    main()
