"""OOM fault-tolerance integration tests.

The reference catches device OOM inside its task loop and skips the
batch (src/ddp_tasks.jl:230-238) with a ``num_missed`` counter that is
declared but never incremented (:178, :240).  Here the counter is live
and the two guard branches (donated state, multi-host) raise with clear
messages — these tests exercise all three paths by injecting a failing
step_fn, the analog of the reference's ``TaskFailedException`` wrapping.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from fluxdistributed_tpu import optim
from fluxdistributed_tpu.data import SyntheticDataset
from fluxdistributed_tpu.models import resnet18
from fluxdistributed_tpu.train import prepare_training, train
from fluxdistributed_tpu.train.logging import NullLogger


def _task(cycles=4, donate=False):
    ds = SyntheticDataset(nsamples=64, nclasses=10, shape=(16, 16, 3))
    return prepare_training(
        resnet18(num_classes=10, dtype=jnp.float32),
        ds,
        optim.momentum(0.1, 0.9),
        batch_size=16,
        cycles=cycles,
        donate=donate,
    )


class _FakeOOM(Exception):
    pass


def _inject_oom_once(task, msg="RESOURCE_EXHAUSTED: fake injected OOM"):
    real = task.step_fn
    calls = {"n": 0}

    def failing(state, batch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise _FakeOOM(msg)
        return real(state, batch)

    task.step_fn = failing
    return calls


def test_oom_skips_batch_and_continues():
    task = _task(cycles=4)
    _inject_oom_once(task)
    train(task, print_every=0, eval_every=0, logger=NullLogger())
    assert task.num_missed == 1
    # 4 cycles, first skipped -> 3 applied steps
    assert int(task.state.step) == 3


def test_non_oom_errors_propagate():
    task = _task(cycles=2)
    _inject_oom_once(task, msg="INVALID_ARGUMENT: something else entirely")
    with pytest.raises(_FakeOOM):
        train(task, print_every=0, eval_every=0, logger=NullLogger())
    assert task.num_missed == 0


def test_oom_with_donated_state_raises():
    class _DeletedLeaf:
        def is_deleted(self):
            return True

    task = _task(cycles=2, donate=True)

    def failing(state, batch):
        # simulate: buffers were donated to the failed execution
        from fluxdistributed_tpu.parallel.dp import TrainState

        task.state = TrainState(
            params={"w": _DeletedLeaf()},
            opt_state=state.opt_state,
            model_state=state.model_state,
            step=state.step,
        )
        raise _FakeOOM("RESOURCE_EXHAUSTED: fake injected OOM")

    task.step_fn = failing
    with pytest.raises(RuntimeError, match="donate=True"):
        train(task, print_every=0, eval_every=0, logger=NullLogger())


# slow tier: secondary cursor/logging assertions on a second full
# trainer build; the core skip-and-continue behavior stays fast
@pytest.mark.slow
def test_oom_skip_advances_cursor_and_logs_global_index():
    """The skipped-step path advances the data cursor and records the
    skipped batch's global index — the bookkeeping resume-after-skip
    parity depends on."""
    logged = []

    class Capture(NullLogger):
        def log(self, metrics, step=None):
            logged.append((dict(metrics), step))

    task = _task(cycles=4)
    _inject_oom_once(task)
    train(task, print_every=0, eval_every=0, logger=Capture())
    assert task.skipped_items == [0]
    assert any(m.get("oom_skipped_item") == 0 for m, _ in logged)
    # cursor advanced past the skip: 4 items consumed, 3 steps applied
    assert int(task.state.step) == 3


def _mlp_task(cycles=5):
    """A cheap task for the resume-parity flow (three prepares; an MLP
    compiles in a fraction of resnet18's time)."""
    from fluxdistributed_tpu.data import SyntheticDataset as DS
    from fluxdistributed_tpu.models import MLP

    ds = DS(nsamples=64, nclasses=10, shape=(8, 8, 3))
    return prepare_training(
        MLP(features=(10, 10)), ds, optim.adam(1e-3),
        batch_size=8, cycles=cycles, topk=())


def test_oom_skip_then_preempt_resume_replays_deterministically(tmp_path):
    """Resume after an OOM-skip: the manifest's cursor counts the
    skipped item, so the resumed run replays the exact remaining
    stream — losses match an uninterrupted run with the same skip."""
    from fluxdistributed_tpu import faults
    from fluxdistributed_tpu.train import read_resume_manifest, resume_training

    def record(task):
        losses = []
        orig = task.step_fn

        def wrapped(state, batch):
            out = orig(state, batch)
            losses.append(float(out[1]["loss"]))
            return out

        task.step_fn = wrapped
        return losses

    # baseline: item 0 OOM-skipped, run to completion
    ta = _mlp_task(cycles=5)
    _inject_oom_once(ta)
    la = record(ta)
    train(ta, print_every=0, eval_every=0, logger=NullLogger())
    assert len(la) == 4  # items 1..4

    # same skip, preempted at item 2, resumed
    tb = _mlp_task(cycles=5)
    _inject_oom_once(tb)
    lb = record(tb)
    faults.install_plan(faults.FaultPlan().sigterm_at_step(2))
    try:
        with pytest.raises(faults.Preempted):
            train(tb, print_every=0, eval_every=0, logger=NullLogger(),
                  checkpoint_dir=str(tmp_path), checkpoint_every=0,
                  handle_signals=True)
    finally:
        faults.clear_plan()
    m = read_resume_manifest(tmp_path)
    assert m["next_item"] == 2          # cursor counts the skipped item
    assert m["checkpoint_step"] == 1    # only item 1 actually stepped
    assert m["num_missed"] == 1
    assert m["skipped_items"] == [0]

    tb2 = _mlp_task(cycles=5)
    lb2 = record(tb2)
    resume_training(tb2, str(tmp_path))
    assert tb2.num_missed == 1 and tb2.skipped_items == [0]
    train(tb2, print_every=0, eval_every=0, logger=NullLogger())
    assert lb + lb2 == la
    assert int(tb2.state.step) == 4


def test_oom_multihost_raises(monkeypatch):
    from fluxdistributed_tpu.parallel import multihost

    task = _task(cycles=2)
    _inject_oom_once(task)
    # Fake a 2-process world for the trainer's guard; keep the loader's
    # batch assembly single-process (it would otherwise try to stitch a
    # half-batch from each "process").
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost, "global_batch_put",
                        lambda x, sharding, batch_dim=0: jax.device_put(x, sharding))
    with pytest.raises(RuntimeError, match="multi-host"):
        train(task, print_every=0, eval_every=0, logger=NullLogger())
