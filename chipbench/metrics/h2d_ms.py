"""Input: milliseconds a prefetch worker spent copying one batch to the
device (``fdtpu_data_h2d_seconds`` over the batches of the window)."""


def read(ctx):
    r = ctx["window"]["registry"]
    if not r["h2d_n"]:
        return None
    return 1e3 * r["h2d_s"] / r["h2d_n"]
