"""Input: share of the wall time of the window's items, those before any
profiler session (``steplog.window_items``), in which no step was in
flight on the device (outside every ``device`` span of the program's
step timeline, ``[dispatch end, completion]``) while the loop sat in
``data_wait``.  With the two other ``starved_*`` shares it sums to the
share of the time in which the host had handed the device nothing: a
host-clock relative of the device's idle share, not a bound on it
(``steplog``'s docstring)."""


def read(ctx):
    from chipbench import steplog

    return steplog.read(ctx, "starved_data_wait_pct")
