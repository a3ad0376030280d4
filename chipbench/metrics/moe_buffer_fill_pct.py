"""Expert layer: of the rows of the sorted buffers the run's expert
layers took, the share that held a token-slot of an expert held here.
The layer's work around its grouped products (gathers, masks, the gate,
their gradients) runs over a buffer's whole length, so the rest is work
on dead rows.  From the program's ``fdtpu_moe_buffer_rows_total``;
nothing to read where the program has no such counter."""


def read(ctx):
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    if reg.get("fdtpu_moe_buffer_rows_total") is None:
        return None
    live = reg.value("fdtpu_moe_buffer_rows_total", "live")
    taken = reg.value("fdtpu_moe_buffer_rows_total", "taken")
    return 100.0 * live / taken if taken else None
