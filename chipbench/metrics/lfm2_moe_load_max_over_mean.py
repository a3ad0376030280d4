"""Expert layer: ``moe_load_max_over_mean`` (its sibling, which holds
the definition and reads the program's ``fdtpu_moe_load_max_over_mean``)
for the cells of a configuration the sibling's entry does not list."""

import os

from chipbench.harness import load_module

read = load_module(os.path.join(os.path.dirname(__file__),
                                "moe_load_max_over_mean.py")).read
