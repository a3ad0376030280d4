"""Cold start: seconds before the window in which the program traced or
lowered (the union of the `trace` and `lower` spans): the host-side part
of every compile, which no cache skips."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "setup_trace_lower_s")
