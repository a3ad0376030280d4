"""The `evabyte` configuration's own parts of the benchmark, on the CPU:
the tiny model through the harness's path, the float8 control and the
half batch failing its tiny limits, a traced run reading the layer's
gauge under the cell's metric name, and the kernel reader counting a
step's work as written down by hand and staying silent where there is
nothing to read."""

import json
import os

import pytest

from chipbench_tiny import ROOT, harness, run_tiny, tiny_cell

from chipbench import reference
from chipbench.pool import make_pool

CELL = "evabyte_t8192_b2_x1"
M = harness.load_manifest()
PEAKS = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
OWED = ("eva_attn_roofline_pct", "eva_summary_pairs_pct")
# the unlisted metrics every cell owes, and the entries listing it beside others
SHARED = ("compile_s", "step_device_ms", "step_mfu_pct", "device_idle_pct",
          "setup_model_init_s", "loop_ahead_steps")


def reader(name):
    return harness.load_module(harness.find_file(ROOT, M, "metrics", name + ".py"))


def full_config():
    with open(os.path.join(ROOT, "chipbench", "configs", "evabyte.json")) as f:
        return json.load(f)


def test_the_cell_owes_its_own_two_and_the_shared_metrics_by_name():
    cell = harness.load_cell(CELL)
    names = [m["name"] for m in cell.metrics["per_layer"]]
    assert set(OWED) | set(SHARED) <= set(names)
    # the other cells' kernel and router entries list those cells alone
    assert not {"attn_roofline_pct", "gqa_attn_roofline_pct", "moe_compact_pct",
                "moe_buffer_fill_pct", "step_interval_p95_ms",
                "allreduce_exposed_ms"} & set(names)
    assert cell.traffic["global_batch"] == 2 and cell.chips == 1
    assert cell.config["input"] == {"kind": "tokens", "seq_len": 8192,
                                    "vocab": 320}


def test_the_file_states_the_cut_and_every_published_number():
    cfg = full_config()
    entry = next(c for c in M["configs"] if c["name"] == "evabyte")
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 4, "num_attention_heads": 8,
        "num_key_value_heads": 8}
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 32}
    # no width is cut, and the program is given what the file states
    kw = cfg["model"]["kwargs"]
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["window_size"],
            cfg["chunk_size"], cfg["num_pred_heads"], cfg["vocab_size"]) == (
        kw["dim"], kw["intermediate_size"], kw["window_size"],
        kw["chunk_size"], kw["num_pred_heads"], kw["vocab"]) == (
        4096, 11008, 2048, 16, 8, 320)
    assert kw["num_heads"] == cfg["layer_heads"] == 32
    assert kw["heads_held"] == cfg["heads_held"] == [0, 8]
    assert kw["dim"] // kw["num_heads"] == 128
    assert kw["rope_theta"] == cfg["rope_theta"] == 100000
    assert kw["norm_eps"] == cfg["rms_norm_eps"] == 1e-5
    assert {"deployment", "assumed", "published"} <= set(cfg)


@pytest.mark.parametrize("mode,rows,correct", [
    ("f32", 1.0, True), ("fp8", 1.0, False), ("bf16", 1.0, False),
    ("f32", 0.5, False)])
def test_the_controls_are_not_correct(mode, rows, correct):
    """The reference one precision down (float8; and bfloat16, which the
    tiny program in float32 is also held apart from), and with half of
    the batch left out, put in the program's place."""
    cell = tiny_cell(CELL)
    pool = make_pool(5, 64, cell.config["input"])
    batches = [pool.take(range(8 * k, 8 * k + 8)) for k in range(3)]
    key = harness.seed_key(5)
    ref = reference.first_steps(cell.config, cell.ref, key, batches)
    got = reference.first_steps(cell.config, cell.ref, key, batches,
                                mode=mode, rows_used=rows)
    ok, table = reference.judge(reference.compare(got, ref, cell.ref), cell.limits)
    assert ok == correct, (mode, rows, table)


def test_traced_run_is_correct_and_reads_the_layers_gauge():
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["feed_mismatch"]["value"] == 0
    got = out["metrics"]
    # rows of two windows of 32 in chunks of 4: 2 * 32 * 33 / 2 exact
    # pairs and 8 * 32 * 1 with a summary
    assert got["eva_summary_pairs_pct"]["value"] == pytest.approx(
        100 * 256 / (256 + 1056))
    assert got["eva_summary_pairs_pct"]["unit"] == "%"
    # no TPU plane in a CPU trace: the kernels' share finds nothing
    assert "eva_attn_roofline_pct" not in got
    assert "stats_gap" not in out["compared"]  # the model has no state


def test_the_summary_share_of_the_cell_and_its_silence(monkeypatch):
    from fluxdistributed_tpu.obs import get_registry
    from fluxdistributed_tpu.ops.eva_attention import eva_pairs

    pairs = eva_pairs(8192, 2048, 16)
    assert round(100 * pairs["summary"] / sum(pairs.values()), 2) == 15.78
    import fluxdistributed_tpu.obs as obs

    class Empty:
        def get(self, name):
            return None
    monkeypatch.setattr(obs, "get_registry", lambda: Empty())
    assert reader("eva_summary_pairs_pct").read({}) is None
    monkeypatch.undo()
    assert get_registry() is not None


def test_eva_attention_work_of_a_step_is_the_hand_count():
    cfg = full_config()
    work = reader("eva_attn_roofline_pct").step_work(cfg, 2)
    calls = 2 * 8 * 4                       # rows, held heads, layers
    pairs = 4 * (2048 * 2049 // 2) + 128 * 2048 * (0 + 1 + 2 + 3)
    assert pairs == 9965568
    product = 2 * pairs * 128 * calls       # one product over the pairs
    row = calls * 128 * 2                   # a position of a tensor, bf16
    exact, late, seen = 8192 * row, 6144 * row, 384 * row
    assert work == {
        "fdtpu_flash_fwd": (2 * product, 4 * exact + 2 * late + 2 * seen),
        "fdtpu_flash_dq": (3 * product, 5 * exact + 3 * late + 2 * seen),
        "fdtpu_flash_dkv": (4 * product, 6 * exact + 2 * late + 4 * seen)}
    # ONE forward call a rematerialised layer: the count does not move
    # with remat, unlike the accepted sibling's
    off = dict(cfg, model={"kwargs": dict(cfg["model"]["kwargs"], remat=False)})
    assert reader("eva_attn_roofline_pct").step_work(off, 2) == work
    # every kernel is bound by its operations at these widths
    for ops, nbytes in work.values():
        assert ops / 197e12 > nbytes / 819e9
    # a configuration without EVA attention has no such work
    glm = harness.load_cell("glm47_flash_t4096_b4_x1").config
    assert reader("eva_attn_roofline_pct").step_work(glm, 4) == {}


@pytest.mark.parametrize("kernels,want", [
    ({"fdtpu_flash_fwd": 0.02, "fdtpu_flash_dkv": 0.04}, "two"),
    ({"fdtpu_flash_fwd": 0.02, "fdtpu_flash_dkv": 0.04, "fdtpu_flash_dq": 0.03},
     "three"), ({"fdtpu_gmm": 3.0}, None), ({}, None)])
def test_eva_roofline_reads_the_kernels_the_trace_names(kernels, want):
    cfg = full_config()
    r = reader("eva_attn_roofline_pct")
    ctx = {"trace": {"steps": 7, "kernels": kernels}, "config": cfg, "chips": 1,
           "traffic": {"global_batch": 2}, "peaks": PEAKS}
    got = r.read(ctx)
    if want is None:
        assert got is None
        assert r.read(dict(ctx, trace=None)) is None
        return
    work = r.step_work(cfg, 2)
    least = sum(work[n][0] / 197e12 for n in kernels)
    assert got == pytest.approx(100 * least * 7 / sum(kernels.values()))
