"""The `phi4_mini_flash` configuration's own parts of the benchmark, on
the CPU: the tiny model through the harness's path (the scan's and the
flash kernels interpreted), the float8 and bfloat16 controls and the
half batch failing its tiny limits, a traced run reading the scan's
gauge under the cell's metric name, and the two kernel readers counting
a step's work as written down by hand and staying silent where there is
nothing to read."""

import json
import math
import os

import pytest

from chipbench_tiny import ROOT, harness, run_tiny, tiny_cell

from chipbench import reference
from chipbench.pool import make_pool

CELL = "phi4_mini_flash_t4096_b2_x1"
M = harness.load_manifest()
PEAKS = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
OWED = ("scan_roofline_pct", "sambay_attn_roofline_pct", "scan_kept_state_pct")
# the unlisted metrics every cell owes, and the entries listing it beside others
SHARED = ("compile_s", "step_device_ms", "step_mfu_pct", "device_idle_pct",
          "hbm_peak_gib", "setup_model_init_s", "loop_ahead_steps")


def reader(name):
    return harness.load_module(harness.find_file(ROOT, M, "metrics", name + ".py"))


def full_config():
    with open(os.path.join(ROOT, "chipbench", "configs", "phi4_mini_flash.json")) as f:
        return json.load(f)


def test_the_cell_owes_its_own_three_and_the_shared_metrics_by_name():
    cell = harness.load_cell(CELL)
    names = [m["name"] for m in cell.metrics["per_layer"]]
    assert set(OWED) | set(SHARED) <= set(names)
    for name in OWED:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "images_per_s_per_chip"
    # the other cells' kernel and router entries list those cells alone
    assert not {"attn_roofline_pct", "gqa_attn_roofline_pct", "eva_attn_roofline_pct",
                "moe_buffer_fill_pct", "step_interval_p95_ms"} & set(names)
    assert cell.traffic["global_batch"] == 2 and cell.chips == 1
    assert cell.config["input"] == {"kind": "tokens", "seq_len": 4096,
                                    "vocab": 25008}


def test_the_file_states_the_cut_and_every_published_number():
    cfg = full_config()
    entry = next(c for c in M["configs"] if c["name"] == "phi4_mini_flash")
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 25008)
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["published"]["vocab_size"] == 200064
    # an eighth of the vocabulary, whole rows
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    kw = cfg["model"]["kwargs"]
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["sliding_window"], cfg["mb_per_layer"],
            cfg["layer_norm_eps"], cfg["vocab_size"], cfg["num_hidden_layers"],
            cfg["layer_offset"]) == (
        kw["dim"], kw["intermediate_size"], kw["num_heads"], kw["num_kv_heads"],
        kw["sliding_window"], kw["mb_per_layer"], kw["norm_eps"], kw["vocab"],
        kw["num_layers"], kw["layer_offset"]) == (
        2560, 10240, 40, 20, 512, 2, 1e-5, 25008, 6, 14)
    assert {k: kw[k] for k in cfg["mamba"]} == cfg["mamba"] == {
        "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160}
    assert kw["published_layers"] == 32 and cfg["tie_word_embeddings"]
    assert {"deployment", "assumed", "published"} <= set(cfg)
    ref = harness.load_module(os.path.join(ROOT, "chipbench", "configs",
                                           "phi4_mini_flash.py"))
    count = lambda c: sum(  # noqa: E731
        math.prod(s) for s, _ in ref.param_shapes(c).values())
    assert count(cfg) == cfg["parameters"] == 697073792
    whole = dict(cfg, num_hidden_layers=32, layer_offset=0,
                 input=dict(cfg["input"], vocab=200064))
    assert count(whole) == cfg["published"]["parameters"]


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


def test_traced_run_is_correct_and_reads_the_scans_gauge():
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["feed_mismatch"]["value"] == 0
    got = out["metrics"]
    # rows of 64 are one chunk: one state kept of 64
    assert got["scan_kept_state_pct"] == {"value": 100 / 64, "unit": "%"}
    # no TPU plane in a CPU trace: the kernels' shares find nothing
    assert "scan_roofline_pct" not in got and "sambay_attn_roofline_pct" not in got
    assert "stats_gap" not in out["compared"]  # the model has no state


def test_the_kept_state_share_is_silent_without_the_gauge(monkeypatch):
    import fluxdistributed_tpu.obs as obs

    class Empty:
        def get(self, name):
            return None
    monkeypatch.setattr(obs, "get_registry", lambda: Empty())
    assert reader("scan_kept_state_pct").read({}) is None


def test_the_scans_work_of_a_step_is_the_hand_count():
    work = reader("scan_roofline_pct").step_work(full_config(), 2)
    # two Mamba layers (14, 16), 2 rows of 4,096, d_inner 5,120, 16
    # states, float32: forward u, delta, B, C read and y written;
    # backward those with dy read and the four gradients written
    wide, narrow = 2 * 2 * 4096 * 5120 * 4, 2 * 2 * 4096 * 16 * 4
    assert work == {"fdtpu_scan_fwd": (0, 3 * wide + 2 * narrow),
                    "fdtpu_scan_bwd": (0, 5 * wide + 4 * narrow)}
    # bound by its bytes: 1.23 and 2.05 ms a step at 819 GB/s
    assert round(work["fdtpu_scan_fwd"][1] / 819e6, 2) == 1.23
    glm = harness.load_cell("glm47_flash_t4096_b4_x1").config
    assert reader("scan_roofline_pct").step_work(glm, 4) == {}


def test_differential_attentions_work_of_a_step_is_the_hand_count():
    work = reader("sambay_attn_roofline_pct").step_work(full_config(), 2)
    window = 512 * 513 // 2 + (4096 - 512) * 512   # pairs a row, layer 15
    square = 4096 * 4097 // 2                        # layers 17 and 19
    pairs = 2 * 2 * (window + 2 * square)            # rows, the pair's calls
    product = 2 * 20 * pairs                         # 20 query heads a call
    calls = 2 * 2 * 3                                # rows, calls, layers
    q, o = calls * 4096 * 20 * 64 * 2, calls * 4096 * 20 * 128 * 2
    k, v = calls * 4096 * 10 * 64 * 2, calls * 4096 * 10 * 128 * 2
    assert work == {
        "fdtpu_flash_fwd": (product * (64 + 128), q + k + v + o),
        "fdtpu_flash_dq": (product * (64 + 128 + 64), q + k + v + o + q),
        "fdtpu_flash_dkv": (product * (64 + 128 + 128 + 64), q + k + v + o + k + v)}
    # every kernel is bound by its operations at these widths
    for ops, nbytes in work.values():
        assert ops / 197e12 > nbytes / 819e9
    glm = harness.load_cell("glm47_flash_t4096_b4_x1").config
    assert reader("sambay_attn_roofline_pct").step_work(glm, 4) == {}


@pytest.mark.parametrize("name,kernels,want", [
    ("scan_roofline_pct", {"fdtpu_scan_fwd": 0.02, "fdtpu_scan_bwd": 0.05}, True),
    ("scan_roofline_pct", {"fdtpu_flash_fwd": 0.02}, False),
    ("sambay_attn_roofline_pct", {"fdtpu_flash_fwd": 0.02, "fdtpu_flash_dkv": 0.04,
                                  "fdtpu_flash_dq": 0.03}, True),
    ("sambay_attn_roofline_pct", {"fdtpu_scan_fwd": 0.02}, False),
    ("sambay_attn_roofline_pct", {}, False)])
def test_the_kernel_readers_read_the_kernels_the_trace_names(name, kernels, want):
    cfg = full_config()
    r = reader(name)
    ctx = {"trace": {"steps": 7, "kernels": kernels}, "config": cfg, "chips": 1,
           "traffic": {"global_batch": 2}, "peaks": PEAKS}
    got = r.read(ctx)
    if not want:
        assert got is None
        assert r.read(dict(ctx, trace=None)) is None
        return
    work = r.step_work(cfg, 2)
    least = sum(max(work[n][0] / 197e12, work[n][1] / 819e9) for n in kernels)
    assert got == pytest.approx(100 * least * 7 / sum(kernels.values()))
