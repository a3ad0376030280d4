"""Cold start: programs compiled before the window because the persistent
cache did not hold them (`compile` spans with `cache == "miss"`); a warm
run expects 0.  A line of notes names them, most seconds first, with the
span each fell in."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "setup_cache_misses")
