"""Collectives: milliseconds per traced step in ``all-reduce*``
operations on the first device while no other operation runs there.
Nothing to read where the step holds no all-reduce."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["steps"] or not t["has_all_reduce"]:
        return None
    return 1e3 * t["allreduce_exposed_s"] / t["steps"]
