"""Device: peak bytes in use on the fullest chip, in GiB, as the
result line's ``memory_peak_bytes`` has it."""


def read(ctx):
    if ctx["memory_peak_bytes"] is None:
        return None
    return ctx["memory_peak_bytes"] / 2 ** 30
