"""Trainer loop: how far the loop ran ahead of the device: the mean, over
the window's items before any profiler session, of the steps handed
over and not yet complete when each item was handed over (`ahead` on
the `device` spans); 0 for a loop in lockstep with the device."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "loop_ahead_steps")
