"""Checkpoint resume + step-timing/profiling — gaps the reference left
open (SURVEY §5: save-only checkpoints, no resume, no profiling; its only
timing hook is ``@timed`` in dead code, src/test.jl:52).
"""

import glob
import os

import numpy as np
import pytest

# tier-2 (slow): checkpoint/resume trainer runs — the tier-1 iteration loop must fit the
# 870s verify window (ROADMAP); CI's slow job still runs this file
pytestmark = pytest.mark.slow

from fluxdistributed_tpu import mesh as mesh_lib, optim, tree as tree_lib
from fluxdistributed_tpu.data import SyntheticDataset
from fluxdistributed_tpu.models import SimpleCNN
from fluxdistributed_tpu.train import (
    prepare_training,
    restore_training,
    train,
)
from fluxdistributed_tpu.train.logging import NullLogger


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.data_mesh(8)


def _task(mesh, cycles=4, seed=0):
    ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(16, 16, 3))
    return prepare_training(
        SimpleCNN(num_classes=4), ds, optim.momentum(0.05, 0.9),
        mesh=mesh, batch_size=16, cycles=cycles, seed=seed,
    )


def test_resume_restores_full_state(mesh, tmp_path):
    from fluxdistributed_tpu.train import latest_step, save_checkpoint

    ckdir = str(tmp_path / "ck")
    task = _task(mesh)
    train(task, print_every=0, eval_every=0, logger=NullLogger(),
          checkpoint_dir=ckdir, checkpoint_every=2)
    assert int(task.state.step) == 4
    # in-loop cadence: checkpoint_every=2 → saved at cycle j=2 = step 3
    assert latest_step(ckdir) == 3
    # save the final state too; resume must pick this (the latest)
    save_checkpoint(task.state, ckdir, int(task.state.step))
    want = {
        "params": tree_lib.to_host(task.state.params),
        "opt": tree_lib.to_host(task.state.opt_state),
    }

    fresh = _task(mesh, seed=99)  # different init — must be overwritten
    restore_training(fresh, ckdir)
    assert int(fresh.state.step) == 4
    # bit-exact round-trip of params AND optimizer momentum buffers
    tree_lib.assert_close(tree_lib.to_host(fresh.state.params), want["params"],
                          rtol=0, atol=0)
    tree_lib.assert_close(tree_lib.to_host(fresh.state.opt_state), want["opt"],
                          rtol=0, atol=0)
    # and training continues from the restored state on the mesh
    train(fresh, print_every=0, eval_every=0, logger=NullLogger())
    assert int(fresh.state.step) == 8


class _CaptureLogger:
    def __init__(self):
        self.metrics = []

    def log(self, m, step):
        self.metrics.append((step, dict(m)))

    def info(self, msg):
        pass


def test_tp_sharded_resume(tmp_path):
    """A tp layout's checkpoints save model-sharded and restore
    model-sharded (the abstract-target path), then training continues."""
    from jax.sharding import PartitionSpec as P

    from fluxdistributed_tpu.data import SyntheticTextDataset
    from fluxdistributed_tpu.models import lm_loss_fn, lm_tiny
    from fluxdistributed_tpu.parallel import Layout
    from fluxdistributed_tpu.train import restore_training

    model = lm_tiny(vocab=32, dtype=np.float32)
    ds = SyntheticTextDataset(vocab=32, seqlen=32)

    def mk(cycles):
        return prepare_training(
            model, ds, optim.adam(1e-3), batch_size=16, cycles=cycles,
            loss_fn=lm_loss_fn(model), topk=(),
            layout=Layout("tp", dp=2, tp=4),
        )

    task = mk(4)
    train(task, print_every=0, eval_every=0, logger=NullLogger(),
          checkpoint_dir=str(tmp_path), checkpoint_every=2)

    task2 = restore_training(mk(3), str(tmp_path))
    emb = task2.state.params["embed"]["embedding"]
    assert emb.sharding.spec == P("model", None)
    assert int(task2.state.step) > 0
    train(task2, print_every=0, eval_every=0, logger=NullLogger())


def test_async_checkpoint_commits(mesh, tmp_path):
    """block=False saves must survive state mutation after the call (the
    device→host snapshot is synchronous) and be fully on disk after
    wait_for_pending — the train loop's contract."""
    import jax

    from fluxdistributed_tpu.train.checkpoint import (
        load_checkpoint,
        save_checkpoint,
        wait_for_pending,
    )

    task = _task(mesh)
    snap = tree_lib.to_host(task.state.params)
    save_checkpoint(task.state, str(tmp_path), 0, block=False)
    # mutate state immediately: the async write must hold the snapshot
    task.state = task.state.replace(
        params=jax.tree.map(lambda x: x * 0.0, task.state.params)
    )
    wait_for_pending()
    restored = load_checkpoint(str(tmp_path), step=0)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_loop_async_checkpoint(mesh, tmp_path):
    """train() uses async saves; files must be complete when train returns."""
    from fluxdistributed_tpu.train import latest_step

    task = _task(mesh, cycles=5)
    train(task, print_every=0, eval_every=0, logger=NullLogger(),
          checkpoint_dir=str(tmp_path), checkpoint_every=2)
    assert latest_step(str(tmp_path)) is not None


def test_throughput_metrics_logged(mesh):
    task = _task(mesh, cycles=6)
    logger = _CaptureLogger()
    train(task, print_every=2, eval_every=0, logger=logger)
    rates = [m for _, m in logger.metrics if "images_per_sec" in m]
    assert rates, "expected steps/images-per-sec at the print cadence"
    assert all(m["images_per_sec"] > 0 and m["steps_per_sec"] > 0 for m in rates)


def test_profiler_trace_written(mesh, tmp_path):
    pdir = str(tmp_path / "trace")
    task = _task(mesh, cycles=4)
    train(task, print_every=0, eval_every=0, logger=NullLogger(),
          profile_dir=pdir, profile_start=1, profile_steps=2)
    traces = glob.glob(os.path.join(pdir, "**", "*.trace.json.gz"), recursive=True) + \
        glob.glob(os.path.join(pdir, "**", "*.xplane.pb"), recursive=True)
    assert traces, f"no trace files under {pdir}"
