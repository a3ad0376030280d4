"""Cold start: what of `setup_s` lies in no span of the program: `setup_s`
less the `prepare` span and every `train` span before the window.
Imports, the backend, the pool, the benchmark's seeded weights and its
recorder.  By construction this, `prepare` and the set-up's `train` spans
sum to `setup_s`."""

import os

from chipbench.harness import load_module

_split = load_module(os.path.join(os.path.dirname(__file__), "setup_split.py"))


def read(ctx):
    return _split.read(ctx, "setup_outside_program_s")
