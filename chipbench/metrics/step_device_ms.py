"""Compiled step: device-busy milliseconds per traced step on the first
device (union of its operations' intervals over the whole steps of the
slice, ``trace.py::step_cycles``)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"]:
        return None
    return 1e3 * t["steps_busy0_s"] / t["steps"]
