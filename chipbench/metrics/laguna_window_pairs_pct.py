"""Attention: of the query-key pairs the held layers attend, weighted by
each layer's query heads, the share that sliding-window layers attend.
From the program's ``fdtpu_attn_pairs{kind}`` (a count at trace time from
the shapes the layers were given); nothing to read where the program has
no such gauge, and 0 where a change drops the window layers' band."""


def read(ctx):
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    if reg.get("fdtpu_attn_pairs") is None:
        return None
    sliding = reg.value("fdtpu_attn_pairs", "sliding")
    pairs = sliding + reg.value("fdtpu_attn_pairs", "full")
    return 100.0 * sliding / pairs if pairs else None
