"""Interrupt-resume loss parity — the acceptance core of the
preemption-tolerance subsystem (docs/robustness.md).

A run SIGTERMed at step k (via the deterministic fault plan), resumed
from its checkpoint + RESUME manifest, must produce step-for-step
identical losses to an uninterrupted run — on the same topology
(bit-identical) and on a DIFFERENT virtual-device count (the elastic
case: ZeRO-1's padded flat optimizer shards re-split for the new mesh;
allclose, since reduction order across a different device count may
legally reassociate).

Fast tier: in-process trainer runs on the 8-virtual-device fake mesh.
Slow tier: bin/driver.py subprocess e2e (SIGTERM → rc 75 → --resume),
including the device-count-change resume, and the fsdp elastic form.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from fluxdistributed_tpu import faults, optim
from fluxdistributed_tpu.data import SyntheticDataset
from fluxdistributed_tpu.mesh import data_mesh
from fluxdistributed_tpu.models import MLP
from fluxdistributed_tpu.train import (
    latest_step,
    prepare_training,
    read_resume_manifest,
    resume_training,
    train,
)
from fluxdistributed_tpu.train.logging import NullLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CYCLES = 6
PREEMPT_AT = 3


@pytest.fixture(autouse=True)
def _clean_plan():
    yield
    faults.clear_plan()


def make_task(mesh=None, cycles=CYCLES, zero1=False, layout=None):
    # MLP (10, 10): deliberately non-multiple-of-8 leaf sizes so the
    # ZeRO-1 flat pad CHANGES between 8- and 4-device meshes (bias 10
    # pads to 16 vs 12) — the elastic re-split is actually exercised
    ds = SyntheticDataset(nsamples=64, nclasses=10, shape=(8, 8, 3))
    return prepare_training(
        MLP(features=(10, 10)), ds, optim.adam(1e-3),
        mesh=mesh, batch_size=8, cycles=cycles, topk=(),
        zero1=zero1, layout=layout)


def record_losses(task):
    """Per-step losses in call order, by wrapping the compiled step."""
    losses = []
    orig = task.step_fn

    def wrapped(state, batch):
        out = orig(state, batch)
        losses.append(float(out[1]["loss"]))
        return out

    task.step_fn = wrapped
    return losses


def run_uninterrupted(**kw):
    task = make_task(**kw)
    losses = record_losses(task)
    train(task, print_every=0, eval_every=0, logger=NullLogger())
    return losses


def run_preempted(tmp_path, at=PREEMPT_AT, **kw):
    """Train under a sigterm-at-step-``at`` plan; returns the losses of
    the steps that ran before the checkpoint-and-exit."""
    task = make_task(**kw)
    losses = record_losses(task)
    faults.install_plan(faults.FaultPlan().sigterm_at_step(at))
    try:
        with pytest.raises(faults.Preempted) as ei:
            train(task, print_every=0, eval_every=0, logger=NullLogger(),
                  checkpoint_dir=str(tmp_path), checkpoint_every=0,
                  handle_signals=True)
    finally:
        faults.clear_plan()
    assert ei.value.step == at
    assert ei.value.next_item == at
    assert len(losses) == at
    return losses


def run_resumed(tmp_path, **kw):
    task = make_task(**kw)
    losses = record_losses(task)
    manifest = resume_training(task, str(tmp_path))
    # checkpoint_dir passed so completion clears the RESUME manifest
    # (what a real resumed run does — bin/driver.py keeps the flag)
    train(task, print_every=0, eval_every=0, logger=NullLogger(),
          checkpoint_dir=str(tmp_path), checkpoint_every=0)
    return losses, manifest


@pytest.fixture(scope="module")
def dp_baseline():
    return run_uninterrupted()


@pytest.fixture(scope="module")
def zero1_baseline():
    return run_uninterrupted(zero1=True)


# ---------------------------------------------------------------------------
# same-topology parity (bit-identical)
# ---------------------------------------------------------------------------


def test_preempt_resume_parity_dp(tmp_path, dp_baseline):
    head = run_preempted(tmp_path)
    m = read_resume_manifest(tmp_path)
    assert m is not None
    assert m["checkpoint_step"] == PREEMPT_AT
    assert m["next_item"] == PREEMPT_AT
    assert m["reason"] == "sigterm"
    assert m["mesh"] == {"data": 8} and m["device_count"] == 8
    assert latest_step(str(tmp_path)) == PREEMPT_AT
    tail, manifest = run_resumed(tmp_path)
    assert manifest is not None
    # step-for-step identical, and bit-identical on the same topology
    assert head + tail == dp_baseline
    # a completed run clears the manifest (stale cursors must not leak
    # into the next resume)
    assert read_resume_manifest(tmp_path) is None


def test_preempt_resume_parity_zero1(tmp_path, zero1_baseline):
    head = run_preempted(tmp_path, zero1=True)
    tail, _ = run_resumed(tmp_path, zero1=True)
    assert head + tail == zero1_baseline


# ---------------------------------------------------------------------------
# elastic: resume on a DIFFERENT virtual-device count
# ---------------------------------------------------------------------------


def test_elastic_resume_dp_8_to_4(tmp_path, dp_baseline):
    head = run_preempted(tmp_path)  # 8 devices
    tail, manifest = run_resumed(tmp_path, mesh=data_mesh(4))
    assert manifest is not None
    np.testing.assert_allclose(
        np.asarray(head + tail), np.asarray(dp_baseline),
        rtol=1e-4, atol=1e-6)


@pytest.mark.slow  # the 4→8 direction below keeps tier-1 coverage
def test_elastic_resume_zero1_8_to_4(tmp_path, zero1_baseline):
    """The trim branch: saved flat shards padded to multiples of 8
    re-split onto a 4-way mesh."""
    head = run_preempted(tmp_path, zero1=True)
    tail, _ = run_resumed(tmp_path, zero1=True, mesh=data_mesh(4))
    np.testing.assert_allclose(
        np.asarray(head + tail), np.asarray(zero1_baseline),
        rtol=1e-4, atol=1e-6)


def test_elastic_resume_zero1_4_to_8(tmp_path, zero1_baseline):
    """The pad branch: flat shards saved on 4 devices (bias 10 padded
    to 12) grow to the 8-way pad (16) on resume."""
    head = run_preempted(tmp_path, zero1=True, mesh=data_mesh(4))
    tail, _ = run_resumed(tmp_path, zero1=True)  # back to all 8
    np.testing.assert_allclose(
        np.asarray(head + tail), np.asarray(zero1_baseline),
        rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# edges
# ---------------------------------------------------------------------------


def test_preempt_without_checkpoint_dir_persists_nothing(tmp_path):
    task = make_task()
    faults.install_plan(faults.FaultPlan().sigterm_at_step(1))
    with pytest.raises(faults.Preempted) as ei:
        train(task, print_every=0, eval_every=0, logger=NullLogger(),
              handle_signals=True)
    assert ei.value.checkpoint_dir is None
    assert not os.listdir(tmp_path)


def test_resume_without_manifest_uses_step_cursor(tmp_path, dp_baseline):
    """A cadence checkpoint from a run killed without signal handling
    (kill -9) still resumes: the cursor derives from the step counter
    (correct whenever nothing was OOM-skipped)."""
    run_preempted(tmp_path)
    os.remove(tmp_path / "RESUME.json")
    tail, manifest = run_resumed(tmp_path)
    assert manifest is None
    assert tail == dp_baseline[PREEMPT_AT:]


def test_resume_on_empty_dir_is_fresh_run(tmp_path):
    task = make_task()
    assert resume_training(task, str(tmp_path / "nothing")) is None
    assert int(task.state.step) == 0
    assert getattr(task.loader, "start", 0) == 0


# three extra prepares; the single-preempt parity above is the tier-1 form
@pytest.mark.slow
def test_fresh_signal_mid_resumed_run_preempts_again(tmp_path):
    """Preemption is re-entrant: a resumed run can itself be preempted
    and resumed, and parity still holds."""
    baseline = run_uninterrupted()
    head = run_preempted(tmp_path, at=2)
    # resumed run preempted again at absolute item 4
    task = make_task()
    mid = record_losses(task)
    resume_training(task, str(tmp_path))
    faults.install_plan(faults.FaultPlan().sigterm_at_step(4))
    with pytest.raises(faults.Preempted):
        train(task, print_every=0, eval_every=0, logger=NullLogger(),
              checkpoint_dir=str(tmp_path), checkpoint_every=0,
              handle_signals=True)
    faults.clear_plan()
    m = read_resume_manifest(tmp_path)
    assert m["next_item"] == 4 and m["checkpoint_step"] == 4
    tail, _ = run_resumed(tmp_path)
    assert head + mid + tail == baseline


# ---------------------------------------------------------------------------
# driver e2e (subprocess; slow tier)
# ---------------------------------------------------------------------------


def _driver_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _driver(extra, tmp_path, devices=8):
    return subprocess.run(
        [sys.executable, os.path.join("bin", "driver.py"),
         "--model", "SimpleCNN", "--dataset", "synthetic",
         "--num-classes", "4", "--image-size", "8",
         "--batch-size", "8", "--cycles", "6",
         "--print-every", "0", "--eval-every", "0",
         "--checkpoint-dir", str(tmp_path / "ck"),
         "--checkpoint-every", "0",
         "--platform", "cpu", "--local-devices", str(devices),
         *extra],
        capture_output=True, text=True, timeout=600, env=_driver_env(),
        cwd=REPO,
    )


@pytest.mark.slow
def test_driver_sigterm_checkpoint_resume_e2e(tmp_path):
    """The whole chain through the CLI: a fault-plan SIGTERM at step 3
    exits with the DISTINCT rc 75 after writing checkpoint + manifest;
    --resume completes the remaining steps; the manifest is cleared."""
    p1 = _driver(["--fault-plan", '{"sigterm_at_step": 3}'], tmp_path)
    assert p1.returncode == faults.PREEMPTED_RC, (
        p1.returncode, p1.stdout[-1500:], p1.stderr[-1500:])
    assert "preempted" in p1.stdout
    ck = tmp_path / "ck"
    manifest = json.loads((ck / "RESUME.json").read_text())
    assert manifest["checkpoint_step"] == 3 and manifest["next_item"] == 3

    p2 = _driver(["--resume"], tmp_path)
    assert p2.returncode == 0, (p2.stdout[-1500:], p2.stderr[-1500:])
    assert "resumed from step 3 at item 3 via RESUME manifest" in p2.stdout
    assert "done: 6 steps" in p2.stdout, p2.stdout[-1500:]
    assert not (ck / "RESUME.json").exists()


@pytest.mark.slow
def test_driver_elastic_resume_different_device_count(tmp_path):
    """Preempt on 8 virtual devices, resume on 4 — the fault plan's
    params knob models the next grant window handing back a smaller
    slice; the elastic restore path re-commits to the new mesh."""
    p1 = _driver(["--fault-plan", '{"sigterm_at_step": 3}'], tmp_path)
    assert p1.returncode == faults.PREEMPTED_RC, p1.stderr[-1500:]
    p2 = _driver(
        ["--resume",
         "--fault-plan", '{"params": {"local_devices": 4}}'],
        tmp_path, devices=4)
    assert p2.returncode == 0, (p2.stdout[-1500:], p2.stderr[-1500:])
    assert "resumed from step 3" in p2.stdout
    assert "done: 6 steps" in p2.stdout, p2.stdout[-1500:]


@pytest.mark.slow
def test_elastic_resume_fsdp(tmp_path):
    """fsdp state (per-leaf fsdp-axis shardings, full global shapes)
    rides the same elastic restore: shapes need no adaptation, only the
    re-commit to the new mesh's shardings."""
    import jax

    from fluxdistributed_tpu.parallel import Layout

    baseline = run_uninterrupted(layout="fsdp")
    head = run_preempted(tmp_path, layout="fsdp")
    tail, _ = run_resumed(
        tmp_path, layout="fsdp",
        mesh=Layout("fsdp", fsdp=4).build_mesh(devs=jax.devices()[:4]))
    np.testing.assert_allclose(
        np.asarray(head + tail), np.asarray(baseline),
        rtol=1e-4, atol=1e-6)
