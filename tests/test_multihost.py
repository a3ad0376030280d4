"""Multi-process (fake multi-host) integration tests.

Spawns real OS processes that form a JAX distributed runtime over
localhost gloo — the CPU stand-in for a TPU pod slice's ICI/DCN.  This
covers the territory the reference's process-DDP mode (src/sync.jl +
bin/driver.jl) occupies but never tests (SURVEY §4: "Multi-process mode
has no tests at all").
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

import pytest

# Every test here spawns real OS processes (multi-minute wall-clock);
# module-level mark so additions inherit it and the tier-1
# ``-m 'not slow'`` lane stays fast — full CI still runs them.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _scrubbed_env() -> dict:
    """Child env without the parent's fake-device/platform pins: the
    worker configures its own platform via jax.config."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_procs(cmds, timeout=600):
    env = _scrubbed_env()
    procs = [
        subprocess.Popen(
            c, cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for c in cmds
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed (rc={p.returncode}):\n{out[-4000:]}"
    return outs


@pytest.mark.slow
def test_two_process_training_and_collectives():
    """2 processes x 2 CPU devices: global batch assembly, a 3-step DP
    train run with cross-process grad all-reduce, replica identity,
    cooperative abort."""
    port = _free_port()
    outs = _run_procs(
        [
            [sys.executable, os.path.join("tests", "_mh_worker.py"), str(i), "2", str(port)]
            for i in range(2)
        ]
    )
    for i, out in enumerate(outs):
        assert f"worker {i}: OK" in out, out[-2000:]
        assert f"worker {i}: device-loop OK" in out, out[-2000:]


@pytest.mark.slow
def test_two_process_pipeline_and_moe():
    """2 processes x 4 CPU devices: the GPipe ppermute ring and the MoE
    dispatch/return all_to_alls cross a real process boundary (the DCN
    stand-in), forward AND backward, with shard-level parity against
    dense references computed locally in each worker."""
    port = _free_port()
    outs = _run_procs(
        [
            [sys.executable, os.path.join("tests", "_mh_ppep_worker.py"),
             str(i), "2", str(port)]
            for i in range(2)
        ]
    )
    for i, out in enumerate(outs):
        assert f"worker {i}: OK" in out, out[-3000:]
        for part in ("PP forward", "PP backward", "1F1B cross-process",
                     "EP forward", "EP backward"):
            assert f"{part} parity OK" in out, (part, out[-3000:])


@pytest.mark.slow
def test_driver_cli_fake_cluster():
    """bin/driver.py end-to-end in manual bring-up mode — the analog of
    the reference's bin/driver.jl session, minus the channel plumbing."""
    port = _free_port()
    common = [
        sys.executable,
        os.path.join("bin", "driver.py"),
        "--model", "SimpleCNN", "--dataset", "synthetic",
        "--num-classes", "10", "--image-size", "24",
        "--batch-size", "8", "--cycles", "3",
        "--opt", "momentum", "--lr", "0.05",
        "--print-every", "1", "--eval-every", "0",
        "--coordinator", f"localhost:{port}",
        "--num-processes", "2", "--platform", "cpu", "--local-devices", "2",
    ]
    outs = _run_procs([common + ["--process-id", str(i)] for i in range(2)])
    assert "done: 3 steps" in outs[0], outs[0][-2000:]
    assert "4 (2/host x 2 hosts)" in outs[0], outs[0][-2000:]


@pytest.mark.slow
def test_driver_cli_fake_cluster_fsdp(tmp_path):
    """Multi-host FSDP end-to-end: params/opt state sharded ACROSS
    processes, training steps, checkpoint saved sharded, resume works —
    covering the cross-process gather (tree.to_host process_allgather)
    and the abstract sharded restore path."""
    port = _free_port()
    ck = str(tmp_path / "ck")
    common = [
        sys.executable,
        os.path.join("bin", "driver.py"),
        "--model", "SimpleCNN", "--dataset", "synthetic",
        "--num-classes", "10", "--image-size", "24",
        "--batch-size", "8", "--cycles", "3",
        "--opt", "momentum", "--lr", "0.05",
        "--print-every", "1", "--eval-every", "0",
        "--layout", "fsdp",
        "--checkpoint-dir", ck, "--checkpoint-every", "2",
        "--coordinator", f"localhost:{port}",
        "--num-processes", "2", "--platform", "cpu", "--local-devices", "2",
    ]
    outs = _run_procs([common + ["--process-id", str(i)] for i in range(2)])
    assert "done: 3 steps" in outs[0], outs[0][-2000:]

    # resume from the sharded checkpoint on a fresh 2-process cluster
    port2 = _free_port()
    common[common.index(f"localhost:{port}")] = f"localhost:{port2}"
    outs = _run_procs(
        [common + ["--process-id", str(i), "--resume"] for i in range(2)]
    )
    assert "resumed from step 3" in outs[0], outs[0][-2000:]
    assert "done: 6 steps" in outs[0], outs[0][-2000:]
