"""Guard the benchmark harness.

bench.py measures on the chip; its construction path, and the contract
that a failed or chipless run exits NON-ZERO with an error line that
names the device, get CI coverage on the fake mesh.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

# tier-2 (slow): bench-harness subprocess runs — the tier-1 iteration loop must fit the
# 870s verify window (ROADMAP); CI's slow job still runs this file
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def bench_mod():
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import bench

    return bench


def test_build_step_runs_one_step(bench_mod):
    step, state, b = bench_mod.build_step(batch=8, size=32)
    state2, m = step(state, b)
    assert float(m["loss"]) > 0
    assert int(state2.step) == 1


def test_fused_steps_advance_state(bench_mod):
    """fuse=k runs k optimizer steps per call (one dispatch), same
    (state, metrics) signature as the plain step."""
    step, state, b = bench_mod.build_step(batch=8, size=32, fuse=4)
    state2, m = step(state, b)
    assert int(state2.step) == 4
    assert float(m["loss"]) > 0
    state3, _ = step(state2, b)
    assert int(state3.step) == 8


def test_step_flops_and_mfu(bench_mod):
    """Cost analysis counts a sane FLOP total WITHOUT a second compile;
    an unknown device_kind (the CPU here) is an error, not None, and a
    known one is plain arithmetic."""
    step, state, b = bench_mod.build_step(batch=8, size=32, donate=False)
    fl = bench_mod.step_flops(step, state, b)
    # ResNet-50 fwd+bwd at 32x32 is ~0.25 GFLOP/img -> total well over 1e8
    assert fl > 1e8, fl
    with pytest.raises(RuntimeError, match="no bf16 peak.*'cpu'"):
        bench_mod.mfu_pct(fl, dt=0.01, nchips=8)
    # direct arithmetic check against a fake peak table entry
    bench_mod._PEAK_BF16_TFLOPS["cpu"] = 1.0  # device_kind == "cpu" on host
    try:
        got = bench_mod.mfu_pct(1e10, dt=0.1, nchips=1)
        assert got == 10.0, got  # 1e10/0.1 = 1e11 FLOP/s = 10% of 1 TFLOP/s
    finally:
        bench_mod._PEAK_BF16_TFLOPS.pop("cpu")


def test_build_step_variant_knobs(bench_mod):
    import jax.numpy as jnp

    step, state, b = bench_mod.build_step(
        batch=8, size=32, donate=False, accum_steps=2,
        norm_dtype=jnp.float32, input_f32=True,
    )
    _, m = step(state, b)
    assert float(m["loss"]) > 0
    assert b["image"].dtype == jnp.float32

    step, state, b = bench_mod.build_step(batch=8, size=32, donate=False, remat=True)
    _, m = step(state, b)
    assert float(m["loss"]) > 0

    step, state, b = bench_mod.build_step(batch=8, size=32, donate=False, s2d=True)
    assert b["image"].shape == (8, 16, 16, 12)  # host-side re-layout fed
    _, m = step(state, b)
    assert float(m["loss"]) > 0


def test_main_emits_error_json_and_nonzero_rc_on_failure(bench_mod, capsys):
    """No chip → main() returns NON-ZERO, and the one JSON line it prints
    is an error line: it names the device jax found and carries no value
    under the metric's name (a CPU timing is never a device number)."""
    assert bench_mod.main() == 1
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert "value" not in out and "unit" not in out
    assert "platform 'cpu'" in out["error"]
    assert (out["platform"], out["device_kind"], out["device_count"]) == (
        "cpu", "cpu", 8)
    assert out["phase"] == "backend_init"
    # the cold-start ledger rides the ERROR json too
    assert out["compile_seconds"] >= 0.0
    assert out["cache_hits"] >= 0 and out["cache_misses"] >= 0
    # the static-health stamp rides the error JSON too (shape only —
    # repo lint cleanliness is bin/lint.py --check's gate, and WIP code
    # with a finding must not fail an unrelated bench test)
    assert {"findings", "new", "by_rule"} <= set(out["lint"])
    assert isinstance(out["guard"], dict)
    # CPU has no memory_stats: unavailable, never fake zeros
    assert out["memory"] == {"available": False}


def test_unknown_device_kind_is_an_error(bench_mod):
    with pytest.raises(RuntimeError, match="add it to _PEAK_BF16_TFLOPS"):
        bench_mod.require_tpu({"platform": "tpu", "device_kind": "TPU v99",
                               "device_count": 1})
    bench_mod.require_tpu({"platform": "tpu", "device_kind": "TPU v5 lite",
                           "device_count": 1})


def test_memory_stamp_static_bytes(bench_mod):
    """memory_stamp(state): live HBM summary (unavailable on CPU) plus
    the exact static bytes of the bench state when it is at hand."""
    import jax.numpy as jnp

    class S:
        params = {"w": jnp.zeros((4, 4), jnp.float32)}
        opt_state = {"m": jnp.zeros((4, 4), jnp.float32)}
        model_state = {}

    out = bench_mod.memory_stamp(S())
    assert out["available"] is False
    assert out["static"]["param_bytes"] == 64
    assert out["static"]["total_bytes"] == 128
    assert "static" not in bench_mod.memory_stamp()


def _tiny_build_step(batch, **kw):
    """A stand-in for build_step so main() is testable in seconds: same
    (step, state, batch) contract, trivial compile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def step(state, b):
        s = state + b["image"].sum()
        return s, {"loss": s}

    return step, jnp.zeros(()), {"image": np.ones((batch, 2), np.float32)}


def test_main_error_json_carries_retryable(bench_mod, monkeypatch, capsys):
    """Error lines classify themselves, and every one is a non-zero
    exit: a backend that is not there is worth another attempt, a code
    failure past it is not."""
    assert bench_mod.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["phase"] == "backend_init" and out["retryable"] is True

    def broken(batch, **kw):
        raise TypeError("injected code bug")

    # steer past the platform check in the test, not through an option
    monkeypatch.setattr(bench_mod, "require_tpu", lambda info: None)
    monkeypatch.setattr(bench_mod, "build_step", broken)
    assert bench_mod.main() == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["phase"] == "build" and out["retryable"] is False
    assert "injected code bug" in out["error"]


def test_main_success_line_names_the_device(bench_mod, monkeypatch, capsys):
    """The success path end to end (platform check and peak table
    steered in the test): rc 0 and a result line that carries platform,
    device_kind and device count."""
    monkeypatch.setattr(bench_mod, "require_tpu", lambda info: None)
    monkeypatch.setattr(bench_mod, "build_step", _tiny_build_step)
    monkeypatch.setattr(bench_mod, "step_flops", lambda *a: 0.0)
    monkeypatch.setattr(bench_mod, "time_compiled_step",
                        lambda *a, **kw: (0.01, 5))
    monkeypatch.setitem(bench_mod._PEAK_BF16_TFLOPS, "cpu", 1.0)
    for stamp in ("lint_stamp", "pp_plan_stamp", "layout_pick_stamp"):
        monkeypatch.setattr(bench_mod, stamp, lambda: {"skipped": True})
    assert bench_mod.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] > 0 and out["unit"] == "images/sec/chip"
    assert (out["platform"], out["device_kind"], out["device_count"]) == (
        "cpu", "cpu", 8)


@pytest.mark.parametrize("env_dir", ["set", None])
def test_cache_dir_follows_the_one_rule(env_dir, tmp_path):
    """bench.py keeps its compile cache where JAX_COMPILATION_CACHE_DIR
    says, exactly; unset, at the fixed .jax_cache/ of the checkout."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "FDTPU_RUNS_LEDGER": ""}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(repo, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    p = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 1, p.stderr[-2000:]  # chipless: non-zero
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["compile_cache_dir"] == want
    assert out["platform"] == "cpu"
