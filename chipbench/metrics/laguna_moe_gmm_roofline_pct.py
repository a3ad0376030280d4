"""Kernels: the grouped matrix products' share of their roofline in a
configuration whose dense layers are named by published index
(``mlp_only_layers``, the held layers from ``layer_offset``):
``moe_gmm_roofline_pct``, its sibling, read with the held dense layers
counted under the key the sibling knows (``first_k_dense_replace``).
The sibling holds what a step's products need, the rows the program
counted and the kernel's name.  Nothing to read where the configuration
has no such list, or the sibling finds nothing."""

import os

from chipbench.harness import load_module

GMM = load_module(os.path.join(os.path.dirname(__file__), "moe_gmm_roofline_pct.py"))


def as_sibling(config: dict):
    """The configuration with its held dense layers under the sibling's
    key; None where it names them by no ``mlp_only_layers``."""
    kw = config["model"]["kwargs"]
    if "mlp_only_layers" not in kw:
        return None
    held = range(kw["layer_offset"], kw["layer_offset"] + kw["num_layers"])
    dense = sum(l in kw["mlp_only_layers"] for l in held)
    return dict(config, model=dict(config["model"], kwargs=dict(
        kw, first_k_dense_replace=dense)))


def read(ctx):
    config = as_sibling(ctx["config"])
    return None if config is None else GMM.read(dict(ctx, config=config))
