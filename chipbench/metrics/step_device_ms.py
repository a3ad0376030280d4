"""Compiled step: device-busy milliseconds per traced step on the first
device (union of its operations' intervals over the traced steps)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["busy0_s"] / t["steps"]
