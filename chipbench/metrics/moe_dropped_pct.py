"""Expert layer: of the token-slots routed to the experts held here, the
share that found no row and was dropped; expected 0.  From the program's
``fdtpu_moe_dropped_total`` and ``fdtpu_moe_slots_total``; nothing to
read where the program has no router."""


def read(ctx):
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    if reg.get("fdtpu_moe_slots_total") is None:
        return None
    held = reg.value("fdtpu_moe_slots_total", "held")
    if not held:
        return None
    return 100.0 * reg.value("fdtpu_moe_dropped_total") / held
