"""`moe_compact_pct` on the CPU: the reader's share of a registry written
by hand, its silence where the program has no such counter, and its
place in the tiny cell's traced run (which the manifest owes it in)."""

import pytest

from chipbench_tiny import ROOT, harness, run_tiny

CELL = "glm47_flash_t4096_b4_x1"
M = harness.load_manifest()


def read():
    return harness.load_module(harness.find_file(
        ROOT, M, "metrics", "moe_compact_pct.py")).read({})


@pytest.mark.parametrize("compact,full,want", [
    (12, 0, 100.0), (9, 3, 75.0), (0, 8, 0.0), (0, 0, None)])
def test_the_share_of_layers_on_the_bounded_buffer(monkeypatch, compact, full, want):
    from fluxdistributed_tpu import obs
    from fluxdistributed_tpu.obs.metrics import Registry

    fresh = Registry()
    monkeypatch.setattr(obs, "get_registry", lambda: fresh)
    assert read() is None  # a program without the counter
    paths = fresh.counter("fdtpu_moe_compact_total", "", ("path",))
    paths.labels(path="compact").inc(compact)
    paths.labels(path="full").inc(full)
    assert read() == want


def test_the_tiny_cell_reads_it_from_the_run():
    """Eight experts of which four are held: the bound is every slot, the
    layer has no branch, and each counts as whole."""
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["compared"]
    assert out["metrics"]["moe_compact_pct"] == {"value": 0.0, "unit": "%"}
