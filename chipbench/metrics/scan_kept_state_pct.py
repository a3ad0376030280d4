"""State-space layer: of the bytes that every position's scan state
would take, the share that the selective scan's forward keeps for its
backward.  From the program's ``fdtpu_scan_state_bytes{kind}`` (set at
trace time from the shapes the scan was given): ``kept`` over ``all``,
the chunk's length's inverse for a scan that keeps one state a chunk,
100 for one that stores every position's.  Nothing to read where the
program has no such gauge."""


def read(ctx):
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    if reg.get("fdtpu_scan_state_bytes") is None:
        return None
    every = reg.value("fdtpu_scan_state_bytes", "all")
    return 100.0 * reg.value("fdtpu_scan_state_bytes", "kept") / every if every else None
