"""Expert layer: of the expert layers the run's steps ran, the share
that took the bounded buffer (the rows this chip can expect to hold)
and not the whole one (a row for every token-slot: a step whose held
slots overflow the bound, or a layer whose bound is all its slots).
From the program's ``fdtpu_moe_compact_total``; nothing to read where
the program has no such counter."""


def read(ctx):
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    if reg.get("fdtpu_moe_compact_total") is None:
        return None
    compact = reg.value("fdtpu_moe_compact_total", "compact")
    layers = compact + reg.value("fdtpu_moe_compact_total", "full")
    return 100.0 * compact / layers if layers else None
