"""Expert layer: ``moe_compact_pct`` (its sibling, which holds the
definition and reads the program's ``fdtpu_moe_compact_total``) for the
cells of a configuration the sibling's entry does not list."""

import os

from chipbench.harness import load_module

read = load_module(os.path.join(os.path.dirname(__file__),
                                "moe_compact_pct.py")).read
