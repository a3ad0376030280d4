"""The `lfm2_8b_a1b` configuration's own parts of the benchmark, on the
CPU: the tiny model through the harness's path, the float8 control
failing its tiny limits, a traced run reading the router's counters
under the cell's own metric names, and the two kernel readers counting a
step's work as written down by hand, through their accepted siblings,
and staying silent where there is nothing to read."""

import json
import os

import pytest

from chipbench_tiny import ROOT, harness, run_tiny, tiny_cell

from chipbench import reference
from chipbench.pool import make_pool

CELL = "lfm2_8b_a1b_t4096_b4_x1"
M = harness.load_manifest()
PEAKS = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
OWED = ("gqa_attn_roofline_pct", "lfm2_moe_gmm_roofline_pct",
        "lfm2_moe_load_max_over_mean", "lfm2_moe_compact_pct")
# the unlisted metrics every cell owes, and the entries listing it beside others
SHARED = ("compile_s", "step_device_ms", "step_mfu_pct", "device_idle_pct",
          "moe_buffer_fill_pct", "setup_model_init_s", "loop_ahead_steps")


def reader(name):
    return harness.load_module(harness.find_file(ROOT, M, "metrics", name + ".py"))


def full_config(name="lfm2_8b_a1b"):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_the_cell_owes_its_own_four_and_the_shared_metrics_by_name():
    cell = harness.load_cell(CELL)
    names = [m["name"] for m in cell.metrics["per_layer"]]
    assert set(OWED) | set(SHARED) <= set(names)
    # the GLM cell's kernel and router entries list that cell alone
    assert not {"attn_roofline_pct", "moe_gmm_roofline_pct", "moe_compact_pct",
                "moe_dropped_pct", "step_interval_p95_ms"} & set(names)
    assert cell.traffic == harness.load_cell("glm47_flash_t4096_b4_x1").traffic


@pytest.mark.parametrize("mode,correct", [("f32", True), ("fp8", False)])
def test_the_float8_control_is_not_correct(mode, correct):
    """The reference one precision down, put in the program's place."""
    cell = tiny_cell(CELL)
    pool = make_pool(5, 64, cell.config["input"])
    batches = [pool.take(range(8 * k, 8 * k + 8)) for k in range(3)]
    key = harness.seed_key(5)
    ref = reference.first_steps(cell.config, cell.ref, key, batches)
    got = reference.first_steps(cell.config, cell.ref, key, batches, mode=mode)
    ok, table = reference.judge(reference.compare(got, ref, cell.ref), cell.limits)
    assert ok == correct, (mode, table)


def test_traced_run_is_correct_and_reads_the_routers_counters():
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["compared"]["feed_mismatch"]["value"] == 0
    got = out["metrics"]
    # 2 experts a token over 8: the fullest of 8 bins is over the mean
    assert 1.0 < got["lfm2_moe_load_max_over_mean"]["value"] < 8.0
    assert got["lfm2_moe_load_max_over_mean"]["unit"] == "ratio"
    # 256 slots a layer, half of the experts held: the bound is every slot
    assert got["lfm2_moe_compact_pct"]["value"] == 0.0
    # no TPU plane in a CPU trace: the kernels' shares find nothing
    assert not {"gqa_attn_roofline_pct", "lfm2_moe_gmm_roofline_pct"} & set(got)
    assert "stats_gap" in out["compared"]


def test_grouped_query_attention_work_of_a_step_is_the_hand_count():
    gqa = reader("gqa_attn_roofline_pct")
    work = gqa.step_work(full_config(), 4)
    pairs = 4096 * 4097 // 2
    product = 2 * pairs * 64 * 4 * 32 * 2        # rows, query heads, layers
    q = 4 * 4096 * 32 * 64 * 2 * 2               # a tensor at 32 heads, bf16
    kv = q // 4                                  # at 8
    assert work == {
        "fdtpu_flash_fwd": (2 * product, 2 * q + 2 * kv),             # q o | k v
        "fdtpu_flash_dq": (3 * product, 3 * q + 2 * kv),              # q dO dQ | k v
        "fdtpu_flash_dkv": (4 * product, 2 * q + 4 * kv)}             # q dO | k v dK dV
    # 2.5 TFLOP a step, 13 ms at the peak: a sixth of the GLM cell's 15.5
    assert round(sum(w[0] for w in work.values()) / 1e12, 1) == 2.5
    # every kernel is bound by its operations, not its bytes
    for ops, nbytes in work.values():
        assert ops / 197e12 > 5 * nbytes / 819e9
    # silent for a configuration without such layers, the GLM cell's among them
    assert gqa.step_work(full_config("glm47_flash"), 4) == {}
    no_attention = full_config()
    no_attention["model"]["kwargs"]["layer_types"] = ["conv"] * 6
    assert gqa.step_work(no_attention, 4) == {}


@pytest.mark.parametrize("kernels,want", [
    ({"fdtpu_flash_fwd": 2 * 0.0027913, "fdtpu_flash_dq": 2 * 0.0041870,
      "fdtpu_flash_dkv": 2 * 0.0055826, "fdtpu_gmm": 1.0}, 50.0),
    ({"fdtpu_flash_fwd": 4 * 0.0027913}, 25.0),
    ({"fdtpu_gmm": 1.0}, None),
])
def test_grouped_query_roofline_reads_the_kernels_the_trace_names(kernels, want):
    ctx = {"trace": {"steps": 1, "kernels": kernels}, "config": full_config(),
           "traffic": {"global_batch": 4}, "chips": 1, "peaks": PEAKS}
    got = reader("gqa_attn_roofline_pct").read(ctx)
    assert got is None if want is None else got == pytest.approx(want, rel=1e-3)
    assert reader("gqa_attn_roofline_pct").read(dict(ctx, trace=None)) is None
    glm = dict(ctx, config=full_config("glm47_flash"))
    assert reader("gqa_attn_roofline_pct").read(glm) is None


def test_grouped_product_share_goes_through_its_sibling(monkeypatch):
    from fluxdistributed_tpu import obs
    from fluxdistributed_tpu.obs.metrics import Registry

    mine, gmm = reader("lfm2_moe_gmm_roofline_pct"), reader("moe_gmm_roofline_pct")
    cfg = full_config()
    # five expert layers of six: the leading dense layer is counted out
    as_sibling = mine.as_sibling(cfg)
    assert as_sibling["model"]["kwargs"]["first_k_dense_replace"] == 1
    assert "first_k_dense_replace" not in cfg["model"]["kwargs"]
    ops, nbytes = gmm.step_work(as_sibling, 81920.0)
    assert ops == 9 * 2 * 81920 * 2048 * 1792
    assert nbytes == (9 * 81920 * (2048 + 1792) * 2
                      + 5 * 8 * 2048 * 1792 * (6 * 2 + 3 * 4))
    ctx = {"trace": {"steps": 2, "kernels": {"fdtpu_gmm": 0.1}},
           "config": cfg, "peaks": PEAKS}
    fresh = Registry()
    monkeypatch.setattr(obs, "get_registry", lambda: fresh)
    for name in OWED[1:]:
        assert reader(name).read(ctx) is None     # a program without the counters
    fresh.counter("fdtpu_moe_slots_total", "", ("where",)).labels(
        where="held").inc(3 * 81920)
    paths = fresh.counter("fdtpu_moe_compact_total", "", ("path",))
    paths.labels(path="compact").inc(12)
    paths.labels(path="full").inc(3)
    balance = fresh.histogram("fdtpu_moe_load_max_over_mean", "", ("layer",),
                              buckets=(1.0, 2.0))
    for layer in range(5):
        for _ in range(3):
            balance.labels(layer=layer).observe(1.5)
    least = max(ops / 197e12, nbytes / 819e9)
    assert mine.read(ctx) == pytest.approx(100 * least * 2 / 0.1)
    assert reader("lfm2_moe_load_max_over_mean").read(ctx) == pytest.approx(1.5)
    assert reader("lfm2_moe_compact_pct").read(ctx) == pytest.approx(80.0)
    # a configuration that counts its dense layers otherwise: nothing
    assert mine.read(dict(ctx, config=full_config("glm47_flash"))) is None
