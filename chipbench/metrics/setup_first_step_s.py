"""Cold start: from the start of the run's first call of `train()` to the
end of its first `device` span: the step traced, lowered, compiled or
loaded from the cache, and run once."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "setup_first_step_s")
