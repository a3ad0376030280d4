"""Trainer loop: share of the wall time of the window's items, those
before any profiler session, in which no step was in flight on the
device while the loop sat in ``dispatch`` (the step being enqueued is in
flight only from the dispatch's end).  Near zero where a slow dispatch
is back-pressure."""


def read(ctx):
    from chipbench import steplog

    return steplog.read(ctx, "starved_dispatch_pct")
