"""Trainer loop: share of the window the loop spent blocked on the
prefetch queue (``fdtpu_train_phase_seconds{phase="data_wait"}``)."""


def read(ctx):
    w = ctx["window"]
    if not w["registry"]["phase_data_wait_n"]:
        return None
    return 100.0 * w["registry"]["phase_data_wait_s"] / w["seconds"]
