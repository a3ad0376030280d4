"""Kernels: the grouped matrix products' share of their roofline in a
configuration whose leading dense layers are counted by
``num_dense_layers``: ``moe_gmm_roofline_pct``, its sibling, read with
that count under the key the sibling knows (``first_k_dense_replace``).
The sibling holds what a step's products need, the rows the program
counted and the kernel's name.  Nothing to read where the configuration
has no such key, or the sibling finds nothing."""

import os

from chipbench.harness import load_module

GMM = load_module(os.path.join(os.path.dirname(__file__), "moe_gmm_roofline_pct.py"))


def as_sibling(config: dict):
    """The configuration with its dense layers under the sibling's key;
    None where it does not count them by ``num_dense_layers``."""
    kw = config["model"]["kwargs"]
    if "num_dense_layers" not in kw:
        return None
    return dict(config, model=dict(config["model"], kwargs=dict(
        kw, first_k_dense_replace=kw["num_dense_layers"])))


def read(ctx):
    config = as_sibling(ctx["config"])
    return None if config is None else GMM.read(dict(ctx, config=config))
