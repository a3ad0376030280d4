"""Expert layer: a step's largest expert load over its mean load, the
mean over the run's steps and expert layers (1 is an even load; the
grouped product's longest group, and across chips the slowest chip,
grow with it).  From the program's ``fdtpu_moe_load_max_over_mean``;
nothing to read where the program has no router."""


def read(ctx):
    try:
        from fluxdistributed_tpu.obs import get_registry
    except ImportError:
        return None
    balance = get_registry().get("fdtpu_moe_load_max_over_mean")
    if balance is None:
        return None
    cells = balance.series().values()
    n = sum(c["count"] for c in cells)
    return sum(c["sum"] for c in cells) / n if n else None
