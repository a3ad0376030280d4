"""Input: a prefetch worker's mean milliseconds in ``assemble`` (the
gather of one batch's rows on the host, before the copy to the device)
per batch, over the window's items before any profiler session."""


def read(ctx):
    from chipbench import steplog

    return steplog.read(ctx, "assemble_ms")
