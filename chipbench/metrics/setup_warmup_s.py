"""Cold start: seconds of the `warmup` and `aot` spans before the window
(`compilation.warmup_train`, `compilation.load_or_compile` and the batch
they compile against); 0 where neither ran."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "setup_warmup_s")
