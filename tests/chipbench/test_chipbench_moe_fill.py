"""`moe_buffer_fill_pct` on the CPU: the reader's share of a registry
written by hand, its silence where the program has no such counter, its
manifest entry's fields, found by its name, and its place in the tiny
expert cells' traced runs (which the manifest owes it in)."""

import pytest

from chipbench_tiny import ROOT, harness, run_tiny

CELLS = ["glm47_flash_t4096_b4_x1", "lfm2_8b_a1b_t4096_b4_x1"]
M = harness.load_manifest()


def read():
    return harness.load_module(harness.find_file(
        ROOT, M, "metrics", "moe_buffer_fill_pct.py")).read({})


@pytest.mark.parametrize("live,taken,want", [
    (84500, 92160, 100.0 * 84500 / 92160),  # five layers on a rung of 18,432
    (16384, 16384, 100.0), (0, 65536, 0.0), (0, 0, None)])
def test_the_share_of_the_taken_rows_that_were_live(monkeypatch, live, taken, want):
    from fluxdistributed_tpu import obs
    from fluxdistributed_tpu.obs.metrics import Registry

    fresh = Registry()
    monkeypatch.setattr(obs, "get_registry", lambda: fresh)
    assert read() is None  # a program without the counter
    rows = fresh.counter("fdtpu_moe_buffer_rows_total", "", ("kind",))
    rows.labels(kind="live").inc(live)
    rows.labels(kind="taken").inc(taken)
    assert read() == want


def test_the_entry_names_the_expert_layer_and_both_expert_cells():
    entry = next(m for m in M["per_layer"] if m["name"] == "moe_buffer_fill_pct")
    assert entry == {
        "name": "moe_buffer_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "expert layer",
        "moves": "images_per_s_per_chip", "workloads": CELLS}
    # the layer's name as the other entries of that layer spell it
    assert entry["layer"] in {m["layer"] for m in M["per_layer"] if m is not entry}
    for w in M["workloads"]:
        owed = [m["name"] for m in harness.load_cell(w["name"]).metrics["per_layer"]]
        assert ("moe_buffer_fill_pct" in owed) == (w["name"] in CELLS)


@pytest.mark.parametrize("cell,compact", [(CELLS[0], "moe_compact_pct"),
                                          (CELLS[1], "lfm2_moe_compact_pct")])
def test_the_tiny_cell_reads_it_from_the_run(cell, compact):
    """Eight experts of which four are held: the only rung is every
    slot, so a layer takes all its slots and about half of them are
    live (the seeded router is near even)."""
    out = run_tiny(cell, trace=True)
    assert out["correct"], out["compared"]
    fill = out["metrics"]["moe_buffer_fill_pct"]
    assert fill["unit"] == "%" and 35.0 < fill["value"] < 65.0
    assert out["metrics"][compact]["value"] == 0.0
