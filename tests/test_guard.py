"""Self-healing training guard (train/guard.py) — the acceptance core.

The contract under test: an injected NaN-grad/loss at step k with the
guard enabled finishes the run with final params BIT-IDENTICAL to a
clean run that deterministically skipped the same batch; anomalies that
persist roll back to the last-good checkpoint and replay with the
quarantined span skipped; rollback loops halt with ``retryable=False``.
Injection rides the ``faults`` value sites (``train.loss`` /
``train.grad`` with ``nan``/``inf`` actions) — RNG-free, recompile-free.

Fast tier: policy-engine units (no jax) + in-process trainer runs on
the 8-virtual-device fake mesh, including the rollback × ZeRO-1 ×
elastic-resume interplay.  Slow tier: bin/driver.py subprocess e2e
(--guard quarantine end-to-end, --replay-step, guard-halt rc 65).
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from fluxdistributed_tpu import faults, optim
from fluxdistributed_tpu.data import SyntheticDataset
from fluxdistributed_tpu.mesh import data_mesh
from fluxdistributed_tpu.models import MLP
from fluxdistributed_tpu.obs.metrics import Registry
from fluxdistributed_tpu.train import (
    GuardConfig,
    GuardHalt,
    TrainGuard,
    prepare_training,
    read_resume_manifest,
    replay_item,
    resume_training,
    train,
)
from fluxdistributed_tpu.train.logging import NullLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CYCLES = 8


@pytest.fixture(autouse=True)
def _clean_plan():
    yield
    faults.clear_plan()


def make_task(mesh=None, cycles=CYCLES, zero1=False):
    ds = SyntheticDataset(nsamples=64, nclasses=10, shape=(8, 8, 3))
    return prepare_training(
        MLP(features=(10, 10)), ds, optim.adam(1e-3),
        mesh=mesh, batch_size=8, cycles=cycles, topk=(),
        zero1=zero1, guard=True)


def record_losses(task):
    losses = []
    orig = task.step_fn

    def wrapped(state, batch):
        out = orig(state, batch)
        losses.append(float(out[1]["loss"]))
        return out

    task.step_fn = wrapped
    return losses


def assert_params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def plan(*entries):
    faults.install_plan(faults.FaultPlan.from_spec({"fail": list(entries)}))


# ---------------------------------------------------------------------------
# policy engine units (no jax, no trainer)
# ---------------------------------------------------------------------------


def guard_with(reg=None, **kw):
    return TrainGuard(GuardConfig(**kw), registry=reg or Registry(),
                      logger=NullLogger())


def test_zscore_warmup_and_spike():
    g = guard_with(warmup=4, zmax=6.0)
    # warmup: non-finite always detected, spikes not yet
    assert g.zscore(99.0) is None
    for i in range(6):
        assert g.observe(i, {"loss": 1.0 + 0.01 * (i % 2)}) == "ok"
    z = g.zscore(50.0)
    assert z is not None and z > 6.0
    assert g.observe(6, {"loss": 50.0}) == "skip"
    assert g.is_quarantined(6)
    # the spike was NOT absorbed into the baseline
    assert g.zscore(50.0) > 6.0
    # and normal losses keep flowing
    assert g.observe(7, {"loss": 1.0}) == "ok"


def test_zscore_zero_mad_epsilon_floor():
    g = guard_with(warmup=4)
    for i in range(5):
        g.observe(i, {"loss": 2.0})  # bit-constant window, MAD = 0
    assert math.isfinite(g.zscore(2.0)) and abs(g.zscore(2.0)) < 1e-6
    assert g.zscore(2.1) > 1e3  # any deviation registers


def test_nonfinite_sentinel_detection():
    g = guard_with(rollback_after=10)  # stay on the skip tier here
    assert g.observe(0, {"guard": np.array([1.0, 0.5])}) == "ok"
    assert g.observe(1, {"guard": np.array([np.nan, 0.5])}) == "skip"
    assert g.observe(2, {"guard": np.array([1.0, np.inf])}) == "skip"
    # loss-only fallback (no compiled sentinel)
    assert g.observe(3, {"loss": np.float32("nan")}) == "skip"
    assert sorted(g.quarantined_items()) == [1, 2, 3]


def test_policy_ladder_rollback_then_halt():
    g = guard_with(rollback_after=2, anomaly_window=8, max_rollbacks=1,
                   progress_steps=4)
    bad = {"guard": np.array([np.nan, 1.0])}
    assert g.observe(0, bad) == "skip"
    assert g.observe(1, bad) == "rollback"      # 2 within the window
    assert g.observe(2, bad) == "skip"          # window reset post-rollback
    assert g.observe(3, bad) == "halt"          # debt 1 == max_rollbacks
    err = g.halt("test")
    assert isinstance(err, GuardHalt) and err.retryable is False
    assert err.quarantined == [0, 1, 2, 3]


def test_progress_clears_rollback_debt():
    g = guard_with(rollback_after=2, anomaly_window=4, max_rollbacks=1,
                   progress_steps=3)
    bad = {"guard": np.array([np.nan, 1.0])}
    assert g.observe(0, bad) == "skip"
    assert g.observe(1, bad) == "rollback"
    for i in range(2, 5):
        assert g.observe(i, {"guard": np.array([1.0, 1.0])}) == "ok"
    # debt cleared: the next persistent anomaly may roll back again
    assert g.observe(10, bad) == "skip"
    assert g.observe(11, bad) == "rollback"


def test_guard_metrics_names():
    reg = Registry()
    g = guard_with(reg=reg)
    g.observe(0, {"guard": np.array([np.nan, 1.0])})
    text = reg.prometheus_text()
    for name in ("fdtpu_guard_anomalies_total", "fdtpu_guard_quarantined_total",
                 "fdtpu_guard_quarantine_size", "fdtpu_guard_last_z",
                 "fdtpu_guard_grad_norm", "fdtpu_guard_rollbacks_total",
                 "fdtpu_guard_halts_total"):
        assert name in text, name
    assert reg.value("fdtpu_guard_anomalies_total", "nonfinite") == 1


def test_guard_config_validation():
    with pytest.raises(ValueError, match="window"):
        GuardConfig(window=1)
    with pytest.raises(ValueError, match="zmax"):
        GuardConfig(zmax=0)
    with pytest.raises(ValueError, match="rollback_after"):
        GuardConfig(rollback_after=0)


# ---------------------------------------------------------------------------
# the compiled sentinel
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sentinel_task():
    return make_task()


def test_sentinel_shape_and_values(sentinel_task):
    task = sentinel_task
    batch = next(iter(task.loader))
    _, m = task.step_fn(task.state, batch)
    g = np.asarray(m["guard"])
    assert g.shape == (2,)
    assert g[0] == np.float32(m["loss"])  # bit-equal when grads finite
    assert g[1] > 0 and np.isfinite(g).all()


def test_sentinel_poisoned_by_nan_input(sentinel_task):
    task = sentinel_task
    batch = next(iter(task.loader))
    bad = dict(batch)
    img = np.asarray(batch["image"]).copy()
    img[0, 0, 0, 0] = np.nan  # one poisoned pixel
    bad["image"] = img
    _, m = task.step_fn(task.state, bad)
    g = np.asarray(m["guard"])
    assert not np.isfinite(g[0])  # the any-reduce caught it


def test_prepare_guard_validation():
    ds = SyntheticDataset(nsamples=16, nclasses=4, shape=(8, 8, 3))
    with pytest.raises(ValueError, match="donate=False"):
        prepare_training(MLP(features=(4,)), ds, optim.adam(1e-3),
                         batch_size=8, cycles=2, topk=(),
                         guard=True, donate=True)
    with pytest.raises(ValueError, match="loss-only"):
        prepare_training(MLP(features=(4,)), ds, optim.adam(1e-3),
                         batch_size=8, cycles=2, topk=(),
                         guard=True, spmd="shard_map")


def test_guard_sentinel_under_fsdp_layout():
    """A layout rides dp.make_train_step, so it takes the sentinel."""
    ds = SyntheticDataset(nsamples=16, nclasses=4, shape=(8, 8, 3))
    task = prepare_training(MLP(features=(4,)), ds, optim.adam(1e-3),
                            batch_size=8, cycles=2, topk=(),
                            guard=True, layout="fsdp")
    _, m = task.step_fn(task.state, next(iter(task.loader)))
    poisoned_loss, gnorm = np.asarray(m["guard"])
    assert poisoned_loss == np.float32(m["loss"])
    assert np.isfinite(gnorm) and gnorm > 0


# ---------------------------------------------------------------------------
# acceptance: quarantine parity (bit-identical to a clean skip run)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def clean_skip2():
    """A clean guarded run that deterministically skips item 2 — the
    parity oracle for every injected-anomaly run below."""
    task = make_task()
    losses = record_losses(task)
    params, _, _ = train(task, print_every=0, eval_every=0,
                         logger=NullLogger(),
                         guard=GuardConfig(quarantine=(2,)))
    return params, losses


@pytest.mark.parametrize("site,action", [("train.loss", "nan"),
                                         ("train.grad", "inf")])
def test_injected_anomaly_matches_clean_skip(clean_skip2, site, action):
    clean_params, clean_losses = clean_skip2
    task = make_task()
    losses = record_losses(task)
    plan({"site": site, "at": 2, "action": action})
    params, _, task = train(task, print_every=0, eval_every=0,
                            logger=NullLogger(), guard=GuardConfig())
    # item 2 was stepped (its loss recorded) then DISCARDED
    assert len(losses) == len(clean_losses) + 1
    del losses[2]
    assert losses == clean_losses
    assert_params_equal(params, clean_params)
    assert task.quarantined_items == [2]


def test_guard_policy_is_transparent_without_anomalies():
    """No anomalies -> the guard policy commits every step: the loss
    stream is bit-identical to the same compiled (guarded) step run
    with no policy engine at all."""
    t1 = make_task()
    l1 = record_losses(t1)
    train(t1, print_every=0, eval_every=0, logger=NullLogger(),
          guard=GuardConfig())
    assert t1.quarantined_items == []
    t2 = make_task()
    l2 = record_losses(t2)
    train(t2, print_every=0, eval_every=0, logger=NullLogger())
    assert l1 == l2


# ---------------------------------------------------------------------------
# rollback tier
# ---------------------------------------------------------------------------


ROLLBACK_CFG = dict(rollback_after=3, anomaly_window=8)


def test_rollback_matches_clean_skip_run(tmp_path):
    clean = make_task(cycles=10)
    clean_params, _, _ = train(
        clean, print_every=0, eval_every=0, logger=NullLogger(),
        guard=GuardConfig(quarantine=(3, 4, 5), **ROLLBACK_CFG))

    task = make_task(cycles=10)
    plan({"site": "train.loss", "at": 3, "action": "nan"},
         {"site": "train.grad", "at": 4, "action": "inf"},
         {"site": "train.loss", "at": 5, "action": "nan"})
    reg_before = _guard_counter("fdtpu_guard_rollbacks_total")
    params, _, task = train(
        task, print_every=0, eval_every=0, logger=NullLogger(),
        checkpoint_dir=str(tmp_path), checkpoint_every=2,
        guard=GuardConfig(**ROLLBACK_CFG))
    assert_params_equal(params, clean_params)
    assert task.quarantined_items == [3, 4, 5]
    assert _guard_counter("fdtpu_guard_rollbacks_total") == reg_before + 1
    # a COMPLETED run clears the guard manifest like any other
    assert read_resume_manifest(tmp_path) is None


def _guard_counter(name):
    from fluxdistributed_tpu.obs import get_registry

    return get_registry().value(name)


def test_rollback_loop_halts_with_manifest(tmp_path):
    task = make_task()
    plan({"site": "train.loss", "times": 99, "action": "nan"})
    with pytest.raises(GuardHalt) as ei:
        train(task, print_every=0, eval_every=0, logger=NullLogger(),
              checkpoint_dir=str(tmp_path), checkpoint_every=2,
              guard=GuardConfig(rollback_after=2, anomaly_window=8,
                                max_rollbacks=1))
    assert ei.value.retryable is False
    # the halt left a consistent (checkpoint, cursor, quarantine) triple
    m = read_resume_manifest(tmp_path)
    assert m is not None and m["reason"] == "guard"
    assert m["quarantined_items"] == ei.value.quarantined
    assert m["checkpoint_step"] == 0 and m["next_item"] == 0


def test_rollback_without_checkpoint_dir_halts():
    task = make_task()
    plan({"site": "train.loss", "times": 99, "action": "nan"})
    with pytest.raises(GuardHalt, match="no checkpoint_dir"):
        train(task, print_every=0, eval_every=0, logger=NullLogger(),
              guard=GuardConfig(rollback_after=2, anomaly_window=8))


# ---------------------------------------------------------------------------
# rollback x ZeRO-1 x resume interplay (the satellite)
# ---------------------------------------------------------------------------


def test_rollback_zero1_sigterm_elastic_resume(tmp_path):
    """Injected-NaN rollback, then SIGTERM, then elastic resume (8->4):
    step-for-step loss-parity with a clean run that skipped the same
    batches.  Every robustness layer stacked: sentinel detection,
    quarantine, rollback replay, checkpoint-on-signal, manifest
    round-trip, ZeRO-1 flat-shard re-split."""
    clean = make_task(cycles=10, zero1=True)
    clean_losses = record_losses(clean)
    train(clean, print_every=0, eval_every=0, logger=NullLogger(),
          guard=GuardConfig(quarantine=(3, 4, 5), **ROLLBACK_CFG))

    task = make_task(cycles=10, zero1=True)
    faults.install_plan(
        faults.FaultPlan.from_spec(
            {"fail": [{"site": "train.loss", "at": 3, "action": "nan"},
                      {"site": "train.loss", "at": 4, "action": "nan"},
                      {"site": "train.grad", "at": 5, "action": "inf"}]}
        ).sigterm_at_step(7))
    head = record_losses(task)
    with pytest.raises(faults.Preempted):
        train(task, print_every=0, eval_every=0, logger=NullLogger(),
              checkpoint_dir=str(tmp_path), checkpoint_every=2,
              handle_signals=True, guard=GuardConfig(**ROLLBACK_CFG))
    faults.clear_plan()
    m = read_resume_manifest(tmp_path)
    assert m is not None and m["next_item"] == 7
    assert m["quarantined_items"] == [3, 4, 5]

    # elastic: the next grant hands back HALF the devices
    resumed = make_task(cycles=10, zero1=True, mesh=data_mesh(4))
    tail = record_losses(resumed)
    manifest = resume_training(resumed, str(tmp_path))
    assert manifest is not None
    assert resumed.quarantined_items == [3, 4, 5]
    train(resumed, print_every=0, eval_every=0, logger=NullLogger(),
          checkpoint_dir=str(tmp_path), checkpoint_every=0,
          guard=GuardConfig(**ROLLBACK_CFG))
    # strip the three discarded anomaly steps (the injected corruption
    # hits the OBSERVED sentinel, so the recorded losses stay finite —
    # only position, not finiteness, identifies them): the ACCEPTED
    # stream must match the oracle
    accepted = _strip_discarded(head, tail)
    np.testing.assert_allclose(
        np.asarray(accepted), np.asarray(clean_losses),
        rtol=1e-4, atol=1e-6)
    assert read_resume_manifest(tmp_path) is None


def _strip_discarded(head, tail):
    """The guarded run's recorded losses minus the three discarded
    anomaly steps (items 3,4,5 stepped once each, then skipped on the
    rollback replay): what remains is the accepted stream."""
    # items run pre-rollback: 0,1,2,3(bad),4(bad),5(bad -> rollback);
    # replay from the step-2 checkpoint skips 3,4,5 -> 6; sigterm at 7.
    return head[:3] + head[6:] + tail


def test_rollback_after_elastic_resume(tmp_path):
    """Anomalies AFTER an 8->4 elastic resume roll back onto a
    checkpoint with the NEW topology's ZeRO-1 flat-pad layout: guarded
    train() re-banks the baseline on start, so the rollback is a plain
    same-topology restore (without the re-bank it would try to restore
    the old device count's pad shapes and fail)."""
    clean = make_task(cycles=10, zero1=True)
    clean_losses = record_losses(clean)
    train(clean, print_every=0, eval_every=0, logger=NullLogger(),
          guard=GuardConfig(quarantine=(6, 7, 8), **ROLLBACK_CFG))

    task = make_task(cycles=10, zero1=True)
    head = record_losses(task)
    faults.install_plan(faults.FaultPlan().sigterm_at_step(6))
    with pytest.raises(faults.Preempted):
        train(task, print_every=0, eval_every=0, logger=NullLogger(),
              checkpoint_dir=str(tmp_path), checkpoint_every=2,
              handle_signals=True, guard=GuardConfig(**ROLLBACK_CFG))
    faults.clear_plan()

    resumed = make_task(cycles=10, zero1=True, mesh=data_mesh(4))
    tail = record_losses(resumed)
    resume_training(resumed, str(tmp_path))
    plan({"site": "train.loss", "at": 6, "action": "nan"},
         {"site": "train.loss", "at": 7, "action": "nan"},
         {"site": "train.grad", "at": 8, "action": "inf"})
    train(resumed, print_every=0, eval_every=0, logger=NullLogger(),
          checkpoint_dir=str(tmp_path), checkpoint_every=2,
          guard=GuardConfig(**ROLLBACK_CFG))
    assert resumed.quarantined_items == [6, 7, 8]
    # tail = items 6,7,8 (stepped then discarded; third triggered the
    # rollback) then the replay skips them and item 9 is accepted
    accepted = head + tail[3:]
    np.testing.assert_allclose(
        np.asarray(accepted), np.asarray(clean_losses),
        rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# replay harness
# ---------------------------------------------------------------------------


def test_replay_item_reproduces_step(sentinel_task):
    task = sentinel_task
    report = replay_item(task, 2)
    assert report["item"] == 2 and report["finite"] is True
    assert report["sentinel"] == "compiled"
    assert len(report["loss"]) == 1 and len(report["grad_norm"]) == 1
    # deterministic: same (seed, process, item) derivation, same state
    again = replay_item(task, 2, debug_nans=False)
    assert again["loss"] == report["loss"]
    with pytest.raises(ValueError, match="outside"):
        replay_item(task, 10**6)


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
         "--checkpoint-every", "0", "--guard",
         "--platform", "cpu", "--local-devices", str(devices),
         *extra],
        capture_output=True, text=True, timeout=600, env=_driver_env(),
        cwd=REPO,
    )


@pytest.mark.slow
def test_driver_guard_quarantine_e2e(tmp_path):
    """--guard + an injected NaN completes the run (quarantining the
    batch), and --replay-step re-executes the quarantined item from the
    checkpoint + cursor for diagnosis."""
    p = _driver(["--fault-plan",
                 '{"fail": [{"site": "train.loss", "at": 2, '
                 '"action": "nan"}]}'], tmp_path)
    assert p.returncode == 0, (p.stdout[-1500:], p.stderr[-1500:])
    assert "guard: nonfinite anomaly at item 2" in (p.stdout + p.stderr)
    assert "done: 5 steps" in p.stdout, p.stdout[-1500:]

    r = _driver(["--resume", "--replay-step", "2"], tmp_path)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    report = json.loads(r.stdout.strip().splitlines()[-1])
    assert report["item"] == 2 and report["finite"] is True


@pytest.mark.slow
def test_driver_guard_halt_rc(tmp_path):
    """A rollback loop exits with the DISTINCT rc 65 (EX_DATAERR) and
    says retryable: false — the supervisor's stop signal."""
    p = _driver(["--checkpoint-every", "2", "--guard-rollback-after", "2",
                 "--fault-plan",
                 '{"fail": [{"site": "train.loss", "times": 99, '
                 '"action": "nan"}]}'], tmp_path)
    assert p.returncode == faults.HALTED_RC, (
        p.returncode, p.stdout[-1500:], p.stderr[-1500:])
    assert "retryable: false" in p.stdout
