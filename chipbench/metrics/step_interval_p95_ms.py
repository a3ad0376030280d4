"""Compiled step: 95th percentile (nearest rank) of the milliseconds
between the completions of two loader items that follow each other,
taken by the program's completion watcher without blocking the loop,
over the window's items before any profiler session.  The traced run's
session starts 4 s into the window, so about 30 intervals of 116-127 ms
lie before it and one or two beyond the percentile: a layer metric that
shows an uneven feed, not yet a tail to hold a change to (an untraced
20 s window holds about 160)."""


def read(ctx):
    from chipbench import steplog

    return steplog.read(ctx, "step_interval_p95_ms")
