"""Attention: of the query-key pairs one row and head of the EVA layer
attends, the share that are a query with a chunk's summary and not with
a key of its own window.  From the program's ``fdtpu_eva_pairs{part}``
(a count at trace time from the shapes the layer was given); nothing to
read where the program has no such gauge, and 0 where a change drops the
summarised part."""


def read(ctx):
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    reg = get_registry()
    if reg.get("fdtpu_eva_pairs") is None:
        return None
    summary = reg.value("fdtpu_eva_pairs", "summary")
    pairs = summary + reg.value("fdtpu_eva_pairs", "local")
    return 100.0 * summary / pairs if pairs else None
