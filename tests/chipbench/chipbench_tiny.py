"""Tiny sizes for the CPU tests of the benchmark: the same code paths as
a run on the chip, at sizes a test can hold.  The tests pass these to
the harness's functions; the command has no option for them."""

import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

PEAKS = {"bf16_tflops": 1.0, "hbm_gb_per_s": 1.0, "hbm_gb": 1.0}
# read at these sizes on the CPU, the tiny program in float32, over seeds
# 1-3: sound runs stay under a third of each limit, and each planted
# fault passes at least one.  The tiny ResNet at this learning rate
# diverges, so its later losses swing; its first step does not.
LIMITS = {
    "resnet50": {"loss1_gap": 0.01, "loss_gap": 2.5, "grad_gap": 0.05,
                 "head_gap": 0.005, "update_gap": 0.2, "stats_gap": 0.06},
    "vit_l16": {"loss1_gap": 1e-3, "loss_gap": 1e-2, "grad_gap": 1e-2,
                "head_gap": 1e-2, "update_gap": 1e-2},
}


def tiny_config(config: dict) -> dict:
    cfg = copy.deepcopy(config)
    if cfg["name"] == "resnet50":
        cfg.update(image=[64, 64, 3], width=8, num_classes=10)
        cfg["model"]["kwargs"] = {"num_classes": 10, "width": 8,
                                  "dtype": "float32"}
    else:
        cfg.update(image=[32, 32, 3], patch=8, depth=2, dim=64, num_heads=4,
                   mlp_dim=128, num_classes=10)
        cfg["model"]["kwargs"] = dict(
            num_classes=10, use_class_token=True, patch=8, depth=2, dim=64,
            num_heads=4, mlp_dim=128, dtype="float32")
    return cfg


def tiny_cell(name: str, chips: int | None = None):
    """The cell at the tiny size; ``chips`` puts it on that many (forced
    host) devices, as a data-parallel cell of the same configuration."""
    cell = harness.load_cell(name)
    cell.chips = chips or cell.chips
    cell.config = tiny_config(cell.config)
    cell.traffic = dict(cell.traffic, global_batch=8 * cell.chips,
                        pool_rows=64, trace_after_s=0.2, trace_for_s=0.3)
    cell.limits = LIMITS[cell.config["name"]]
    return cell


def run_tiny(name, seed=1, seconds=1.0, trace=False, chips=None):
    """One run of a cell at the tiny size, past the look for a chip."""
    import jax

    cell = tiny_cell(name, chips)
    return harness.run_cell(
        cell, seed, seconds, trace, t_process=time.perf_counter(),
        devices=jax.devices()[:cell.chips], peaks=PEAKS)
