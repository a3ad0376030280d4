"""`correct` comes out false when the timed path is broken underneath:
the run skips the harness's look for a chip and drives the rest, once for
each fault a training cell can have."""

import jax
import pytest

from chipbench_tiny import run_tiny


def _break(monkeypatch, kind):
    """Plant a fault under the timed path: the program's step maker."""
    from fluxdistributed_tpu.train import trainer

    real = trainer.make_train_step

    def maker(loss_fn, optimizer, mesh, **kw):
        if kind == "state_unchanged":
            step = real(loss_fn, optimizer, mesh, **kw)
            return lambda state, batch: (state, step(state, batch)[1])
        part = {"half_batch": 2, "no_exchange": 4}[kind]

        def partial_loss(params, mstate, batch, train, rng=None):
            some = jax.tree.map(lambda x: x[: x.shape[0] // part], batch)
            return loss_fn(params, mstate, some, train, rng=rng)
        return real(partial_loss, optimizer, mesh, **kw)

    monkeypatch.setattr(trainer, "make_train_step", maker)


@pytest.mark.parametrize("name,chips,kind", [
    ("vit_l16_b32_x1", None, "state_unchanged"),
    ("vit_l16_b32_x1", None, "half_batch"),
    ("resnet50_b256_x1", None, "half_batch"),
    ("vit_l16_b32_x1", 4, "no_exchange"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, chips, kind):
    _break(monkeypatch, kind)
    out = run_tiny(name, chips=chips)
    assert not out["correct"], out["compared"]
    failed = [k for k, r in out["compared"].items() if r["value"] > r["limit"]]
    assert failed
    if kind == "state_unchanged":  # reads 1 by the measure: nothing moved
        assert out["compared"]["update_gap"]["value"] == pytest.approx(1.0, abs=0.05)


def test_an_altered_row_in_the_feed_is_not_correct(monkeypatch):
    from fluxdistributed_tpu.data import loader

    real = loader.batch_to_dict

    def altered(out, nclasses=None, one_hot=True):
        d = real(out, nclasses, one_hot)
        d["label"] = d["label"][::-1].copy()  # the answers handed on in another order
        return d

    monkeypatch.setattr(loader, "batch_to_dict", altered)
    out = run_tiny("vit_l16_b32_x1")
    assert not out["correct"]
    assert out["compared"]["feed_mismatch"]["value"] > 0
