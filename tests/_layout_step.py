"""What ``prepare_training(layout=...)`` builds, at the parallel layer:
the state placed by the rule-derived specs and the one dp step compiled
with those shardings.  Tests use it where they need their own
params, a threshold small enough to split a toy model's leaves, or the
step without a loader."""

from fluxdistributed_tpu.parallel import TrainState, make_train_step
from fluxdistributed_tpu.parallel import layout as layout_lib


def layout_step(model, params, opt, loss_fn, layout, *, min_size=64,
                **step_kw):
    """``(mesh, state, step)`` for ``layout`` (a Layout or preset name
    over the 8 test devices)."""
    layout = layout_lib.resolve_layout(layout)
    mesh = layout.build_mesh()
    state, sh = layout_lib.shard_state(
        model, TrainState.create(params, opt), layout, mesh,
        min_size=min_size)
    step = make_train_step(
        loss_fn, opt, mesh, axis=layout.batch_axes,
        state_shardings=sh, **{"donate": False, **step_kw})
    return mesh, state, step
