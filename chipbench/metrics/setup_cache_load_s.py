"""Cold start: seconds the persistent cache took to fetch and load the
programs it served before the window (the sum of `load_s` over the
`compile` spans)."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "setup_cache_load_s")
