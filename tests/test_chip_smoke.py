"""chip_smoke.py rehearsed without the chip (the on-chip-measurement
guide's rehearsals 1 and 2): the same phases at a tiny size on the CPU,
steered through the ``size`` argument of ``main`` — a function argument
the test passes, never a CLI option — plus the refusal to run when the
platform is not a TPU."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, REPO)
    import chip_smoke

    return chip_smoke


@pytest.fixture
def restore_cache_config():
    """main() turns the persistent cache on; leave the worker's config as
    it was for the files that run after this one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from fluxdistributed_tpu import compilation

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()
    compilation._cache_dir = None


def _tiny(smoke):
    return dataclasses.replace(
        smoke.FULL, platform="cpu",
        model="resnet18", classes=10, image=32, per_chip_batch=2, steps=4,
        params_millions=None,
        heads=4, kv_heads=2, head_dim=16, attn_batch=1, seqs=(128, 256),
        window=64, sinks=2, decode_batch=2, cache_rows=64,
        pool_blocks=16, pool_block_rows=8, adam_elems=3000,
        dp_model="resnet18", dp_batch=16, dp_steps=3)


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_phases_at_tiny_size_on_cpu(smoke, capsys, monkeypatch, tmp_path,
                                    restore_cache_config):
    """Rehearsal 1: train → kernels (interpreted) → cache, end to end,
    and the last line is the contract's JSON object and nothing else."""
    import jax

    from fluxdistributed_tpu import compilation

    # what a process started with the variable set looks like: jax read
    # it at import, the program sets no directory of its own
    monkeypatch.setenv(compilation.CACHE_DIR_ENV, str(tmp_path / "cc"))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    assert smoke.main([], size=_tiny(smoke)) == 0
    out = capsys.readouterr().out
    last = _last_json(out)
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    for phase in ("[train]", "[kernels] flash_fwd", "[kernels] flash_bwd",
                  "[kernels] flash_decode dense", "[kernels] flash_decode "
                  "ring+sinks", "[kernels] flash_decode int8",
                  "[kernels] flash_decode_paged", "[kernels] fused_adam",
                  f"[cache] directory {tmp_path / 'cc'}"):
        assert phase in out, phase
    assert "[dp4]" not in out
    assert os.listdir(tmp_path / "cc"), "no cache entries written"


def test_chips4_phase_runs_only_the_dp_comparison(smoke, capsys,
                                                  restore_cache_config):
    """Rehearsal 2: the --chips 4 comparison on 4 virtual devices with
    resnet18 — both four-device paths agree with the one-device run —
    and no other phase runs.  (The exactly-4-devices check is the chip
    run's; the CPU mesh has 8 and the phase takes the first 4.)"""
    size = _tiny(smoke)
    device = {"platform": "cpu", "kind": "cpu", "count": 4}
    smoke.run_dp4(size, device)
    out = capsys.readouterr().out
    assert "[dp4] four devices, spmd=jit" in out
    assert "[dp4] four devices, spmd=shard_map" in out
    assert "[train]" not in out and "[kernels]" not in out
    with pytest.raises(SystemExit, match="need 4"):
        smoke.main(["--chips", "4"], size=size)  # 8 CPU devices, not 4


def test_exits_nonzero_and_prints_no_ok_line_on_cpu():
    """As the driver runs it in the sandbox: no accelerator → another
    exit code than 0 and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "not 'tpu'" in p.stderr
