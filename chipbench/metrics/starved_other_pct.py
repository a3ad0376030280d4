"""Trainer loop: share of the wall time of the window's items, those
before any profiler session, in which no step was in flight on the
device while the loop sat in neither ``data_wait`` nor ``dispatch``: its
own bookkeeping, eval, checkpoint."""


def read(ctx):
    from chipbench import steplog

    return steplog.read(ctx, "starved_other_pct")
