"""Expert layer: of the rows of the sorted buffers the run's expert
layers took, the share that held a token-slot of an expert held here, in
the cell whose held expert sees 1/32 of its share:
``moe_buffer_fill_pct``'s reader under this cell's own name (it holds
what is counted, from ``fdtpu_moe_buffer_rows_total``).  Nothing to read
where the program has no such counter."""

import os

from chipbench.harness import load_module

FILL = load_module(os.path.join(os.path.dirname(__file__), "moe_buffer_fill_pct.py"))


def read(ctx):
    return FILL.read(ctx)
