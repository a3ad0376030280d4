"""Trainer loop: host milliseconds to enqueue one compiled step
(``fdtpu_train_phase_seconds{phase="dispatch"}`` over the steps)."""


def read(ctx):
    r = ctx["window"]["registry"]
    if not r["phase_dispatch_n"]:
        return None
    return 1e3 * r["phase_dispatch_s"] / r["phase_dispatch_n"]
