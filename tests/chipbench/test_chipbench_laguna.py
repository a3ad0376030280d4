"""The `laguna_s21` configuration's own parts of the benchmark, on the
CPU: the tiny model through the harness's path, the float8 control
failing its tiny limits, a traced run
reading the window's and the expert buffer's counters under the cell's
metric names, the file's cut and published numbers, and the two kernel
readers counting a step's work as written down by hand and staying
silent where there is nothing to read."""

import json
import math
import os

import pytest

from chipbench_tiny import ROOT, harness, run_tiny, tiny_cell

from chipbench import reference
from chipbench.pool import make_pool

CELL = "laguna_s21_t4096_b2_x1"
M = harness.load_manifest()
PEAKS = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
OWED = ("laguna_attn_roofline_pct", "laguna_window_pairs_pct",
        "laguna_moe_gmm_roofline_pct", "laguna_moe_buffer_fill_pct")
# the unlisted metrics every cell owes, and the entries listing it beside others
SHARED = ("compile_s", "step_device_ms", "step_mfu_pct", "device_idle_pct",
          "hbm_peak_gib", "setup_model_init_s", "loop_ahead_steps")


def reader(name):
    return harness.load_module(harness.find_file(ROOT, M, "metrics", name + ".py"))


def full_config():
    with open(os.path.join(ROOT, "chipbench", "configs", "laguna_s21.json")) as f:
        return json.load(f)


def test_the_cell_owes_its_own_four_and_the_shared_metrics_by_name():
    cell = harness.load_cell(CELL)
    names = [m["name"] for m in cell.metrics["per_layer"]]
    assert set(OWED) | set(SHARED) <= set(names)
    for name in OWED:
        entry = next(m for m in M["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "images_per_s_per_chip"
    # the other cells' kernel and router entries list those cells alone
    assert not {"attn_roofline_pct", "gqa_attn_roofline_pct", "moe_gmm_roofline_pct",
                "moe_buffer_fill_pct", "sambay_attn_roofline_pct"} & set(names)
    assert cell.traffic["global_batch"] == 2 and cell.chips == 1
    assert cell.config["input"] == {"kind": "tokens", "seq_len": 4096,
                                    "vocab": 12544}


def test_the_file_states_the_cut_and_every_published_number():
    cfg = full_config()
    entry = next(c for c in M["configs"] if c["name"] == "laguna_s21")
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "mlp_only_layers", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["mlp_only_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, [], 8, 12544)
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 48, "mlp_only_layers": [0], "num_experts": 256,
        "vocab_size": 100352}
    # an eighth of the vocabulary, whole rows; 8 of 256 experts, routed over all
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    kw = cfg["model"]["kwargs"]
    assert kw["experts_held"] == cfg["experts_held"] == [0, 8]
    assert kw["n_routed_experts"] == cfg["router_experts"] == 256
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["moe_routed_scaling_factor"], cfg["sliding_window"],
            cfg["rms_norm_eps"], cfg["vocab_size"], cfg["num_hidden_layers"]) == (
        kw["dim"], kw["head_dim"], kw["num_kv_heads"], kw["intermediate_size"],
        kw["moe_intermediate_size"], kw["shared_expert_intermediate_size"],
        kw["num_experts_per_tok"], kw["routed_scaling_factor"],
        kw["sliding_window"], kw["norm_eps"], kw["vocab"], kw["num_layers"]) == (
        3072, 128, 8, 12288, 1024, 1024, 10, 2.5, 512, 1e-6, 12544, 4)
    assert kw["layer_types"] == cfg["layer_types"]
    assert kw["num_heads_per_layer"] == cfg["num_attention_heads_per_layer"]
    assert kw["layer_offset"] == cfg["layer_offset"] == 4
    full, sliding = (cfg["rope_parameters"][k] for k in ("full_attention",
                                                         "sliding_attention"))
    assert (kw["rope_theta"], kw["partial_rotary_factor"], kw["yarn_factor"],
            kw["yarn_original_max_positions"], kw["yarn_beta_fast"],
            kw["yarn_beta_slow"], kw["yarn_attention_factor"]) == (
        full["rope_theta"], full["partial_rotary_factor"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"])
    assert kw["sliding_rope_theta"] == sliding["rope_theta"]
    assert {"deployment", "assumed", "published"} <= set(cfg)
    ref = harness.load_module(os.path.join(ROOT, "chipbench", "configs", "laguna_s21.py"))
    count = lambda c: sum(  # noqa: E731
        math.prod(s) for s, _ in ref.param_shapes(c).values())
    assert count(cfg) == cfg["parameters"] == 653577216
    whole = dict(cfg, num_hidden_layers=48, layer_offset=0, mlp_only_layers=[0],
                 experts_held=[0, 256], input=dict(cfg["input"], vocab=100352))
    assert count(whole) == cfg["published"]["parameters"]
    # 376.5M multiply-adds a token forward, as counted by hand from the widths
    assert round(ref.forward_macs(cfg) / 4096 / 1e6, 1) == 376.5


def test_the_float8_control_is_not_correct():
    """The reference one precision down (float8) put in the program's
    place fails the tiny limits, and the reference itself passes.  (The
    reference in bfloat16, which the tiny program in float32 is also held
    apart from, and half of the batch left out were read once and fail
    by more: the tiny file's ``set_from``.)"""
    cell = tiny_cell(CELL)
    pool = make_pool(5, 64, cell.config["input"])
    batches = [pool.take(range(8 * k, 8 * k + 8)) for k in range(3)]
    key = harness.seed_key(5)
    ref = reference.first_steps(cell.config, cell.ref, key, batches)
    for got, correct in ((ref, True), (reference.first_steps(
            cell.config, cell.ref, key, batches, mode="fp8"), False)):
        ok, table = reference.judge(reference.compare(got, ref, cell.ref),
                                    cell.limits)
        assert ok == correct, table


def test_traced_run_is_correct_and_reads_the_programs_counters():
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["feed_mismatch"]["value"] == 0
    got = out["metrics"]
    # rows of 32 under a window of 16, heads 6 (full) and 9 (sliding)
    t, w = 32, 16
    sliding = 9 * (w * (w + 1) // 2 + (t - w) * w)
    full = 6 * t * (t + 1) // 2
    assert got["laguna_window_pairs_pct"] == {
        "value": pytest.approx(100 * sliding / (sliding + full)), "unit": "%"}
    assert 0 < got["laguna_moe_buffer_fill_pct"]["value"] <= 100
    # no TPU plane in a CPU trace: the kernels' shares find nothing
    assert "laguna_attn_roofline_pct" not in got
    assert "laguna_moe_gmm_roofline_pct" not in got
    assert "stats_gap" not in out["compared"]


@pytest.mark.parametrize("name", ["laguna_window_pairs_pct", "laguna_moe_buffer_fill_pct"])
def test_the_counter_readers_are_silent_without_the_counters(monkeypatch, name):
    import fluxdistributed_tpu.obs as obs

    class Empty:
        def get(self, name):
            return None
    monkeypatch.setattr(obs, "get_registry", lambda: Empty())
    assert reader(name).read({}) is None


def test_the_attention_work_of_a_step_is_the_hand_count():
    work = reader("laguna_attn_roofline_pct").step_work(full_config(), 2)
    band = 512 * 513 // 2 + (4096 - 512) * 512     # pairs a row and head, sliding
    square = 4096 * 4097 // 2                      # full
    product = 2 * 2 * (3 * 72 * band + 48 * square) * 128   # rows; 2 x pairs x width
    q = 2 * 4096 * (3 * 72 + 48) * 128 * 2                   # bf16, query heads
    kv = 2 * 4096 * 4 * 8 * 128 * 2                          # 8 key-value heads a layer
    assert work == {
        "fdtpu_flash_fwd": (2 * product, 2 * q + 2 * kv),
        "fdtpu_flash_dq": (3 * product, 3 * q + 2 * kv),
        "fdtpu_flash_dkv": (4 * product, 2 * q + 4 * kv)}
    # every kernel is bound by its operations at these widths
    for ops, nbytes in work.values():
        assert ops / 197e12 > nbytes / 819e9
    glm = harness.load_cell("glm47_flash_t4096_b4_x1").config
    assert reader("laguna_attn_roofline_pct").step_work(glm, 4) == {}


def test_the_grouped_products_work_is_the_siblings_at_this_cells_widths(monkeypatch):
    r = reader("laguna_moe_gmm_roofline_pct")
    cfg = full_config()
    mine = r.as_sibling(cfg)
    assert mine["model"]["kwargs"]["first_k_dense_replace"] == 0
    # 4 expert layers of 8 held at 3,072 x 1,024: 9 products on the rows,
    # the weights read by 6 in bf16 and their 3 gradients written in f32
    ops, nbytes = r.GMM.step_work(mine, 81920 * 8 / 256 * 4)
    rows = 2560 * 4
    assert ops == 9 * 2 * rows * 3072 * 1024
    assert nbytes == 9 * rows * (3072 + 1024) * 2 + 4 * 8 * 3072 * 1024 * (12 + 12)
    lfm2 = harness.load_cell("lfm2_8b_a1b_t4096_b4_x1").config
    assert r.as_sibling(lfm2) is None
    ctx = {"trace": {"steps": 7, "kernels": {"fdtpu_gmm": 0.1}}, "config": cfg,
           "chips": 1, "traffic": {"global_batch": 2}, "peaks": PEAKS}
    monkeypatch.setattr(r.GMM, "rows_per_step", lambda: None)
    assert r.read(ctx) is None


@pytest.mark.parametrize("kernels,want", [
    ({"fdtpu_flash_fwd": 0.02, "fdtpu_flash_dkv": 0.04, "fdtpu_flash_dq": 0.03}, True),
    ({"fdtpu_gmm": 0.02}, False), ({}, False)])
def test_the_attention_reader_reads_the_kernels_the_trace_names(kernels, want):
    cfg = full_config()
    r = reader("laguna_attn_roofline_pct")
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
