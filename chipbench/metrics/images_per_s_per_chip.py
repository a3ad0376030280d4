"""End to end: images of every optimizer step completed in the window,
over the wall seconds from the call of ``train`` to the barrier on the
final state, per chip.  Host clock."""


def read(ctx):
    w = ctx["window"]
    return w["images"] / w["seconds"] / ctx["chips"]
