"""Cold start: seconds of the `model_init` spans before the window: the
dummy batch, the un-jitted `model.init` with its op-by-op compiles, the
optimizer state and its placement.  Nothing to read where the program
keeps no `train` span (`setup_split.py` holds the definitions)."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "setup_model_init_s")
