"""The `glm47_flash` configuration's own parts of the benchmark, on the
CPU: the float8 control fails its tiny limits, a traced run reads the
router's counters, and the two roofline readers count a step's work as
written down by hand and stay silent where there is nothing to read."""

import json
import os

import pytest

from chipbench_tiny import ROOT, harness, run_tiny, tiny_cell

from chipbench import reference
from chipbench.pool import make_pool

CELL = "glm47_flash_t4096_b4_x1"
M = harness.load_manifest()
PEAKS = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}


def reader(name):
    return harness.load_module(harness.find_file(ROOT, M, "metrics", name + ".py"))


def full_config():
    with open(os.path.join(ROOT, "chipbench", "configs", "glm47_flash.json")) as f:
        return json.load(f)


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


def test_traced_run_reads_the_routers_counters_and_no_device_share():
    out = run_tiny(CELL, trace=True)
    assert out["correct"], out["compared"]
    got = out["metrics"]
    assert got["moe_dropped_pct"]["value"] == 0.0
    # 2 experts a token over 8: the fullest of 8 bins is over the mean
    assert 1.0 < got["moe_load_max_over_mean"]["value"] < 8.0
    assert got["moe_load_max_over_mean"]["unit"] == "ratio"
    # no TPU plane in a CPU trace: the kernels' shares find nothing
    assert not {"attn_roofline_pct", "moe_gmm_roofline_pct"} & set(got)
    assert "stats_gap" in out["compared"]


def test_attention_work_of_a_step_is_the_hand_count():
    cfg = full_config()
    work = reader("attn_roofline_pct").step_work(cfg, 4)
    pairs = 4096 * 4097 // 2
    product = 2 * pairs * 256 * 4 * 20 * 5       # rows, heads, layers
    tensor = 4 * 4096 * 20 * 256 * 2 * 5
    # the forward once, though the layer is rematerialised: a kernel's
    # work is counted once a step, whatever recomputes it
    assert cfg["model"]["kwargs"]["remat"]
    assert work == {"fdtpu_flash_fwd": (2 * product, 4 * tensor),
                    "fdtpu_flash_dq": (3 * product, 5 * tensor),
                    "fdtpu_flash_dkv": (4 * product, 6 * tensor)}
    # 15.5 TFLOP a step, 79 ms at the peak
    assert round(sum(w[0] for w in work.values()) / 1e12, 1) == 15.5
    from fluxdistributed_tpu.ops.pallas_attention import KERNEL_NAMES
    assert tuple(work) == KERNEL_NAMES


@pytest.mark.parametrize("kernels,steps,want", [
    # all three kernels at twice their least time: half of the roofline
    ({"fdtpu_flash_fwd": 2 * 0.017446, "fdtpu_flash_dq": 2 * 0.026169,
      "fdtpu_flash_dkv": 2 * 0.034892}, 1.0, 50.0),
    # only the kernels the trace names are counted, work and time alike
    ({"fdtpu_flash_fwd": 4 * 0.017446}, 1.0, 25.0),
    # a slice that holds two and a half steps
    ({"fdtpu_flash_fwd": 10 * 0.017446}, 2.5, 25.0),
    ({"fdtpu_gmm": 1.0}, 1.0, None),
])
def test_attention_roofline_reads_the_kernels_the_trace_names(kernels, steps, want):
    ctx = {"trace": {"steps": steps, "kernels": kernels}, "config": full_config(),
           "traffic": {"global_batch": 4}, "chips": 1, "peaks": PEAKS}
    got = reader("attn_roofline_pct").read(ctx)
    assert got is None if want is None else got == pytest.approx(want, rel=1e-3)
    # a configuration without latent attention, or no trace: nothing
    plain = dict(ctx, config={"model": {"kwargs": {"num_classes": 10}},
                              "input": {"kind": "images"}})
    assert reader("attn_roofline_pct").read(plain) is None
    assert reader("attn_roofline_pct").read(dict(ctx, trace=None)) is None


def test_grouped_product_work_and_its_silence_without_counters(monkeypatch):
    from fluxdistributed_tpu import obs
    from fluxdistributed_tpu.obs.metrics import Registry

    gmm = reader("moe_gmm_roofline_pct")
    cfg = full_config()
    ops, nbytes = gmm.step_work(cfg, 32768.0)
    # 4 layers x 8,192 rows; 9 products of 2 x rows x 2048 x 1536, the
    # rematerialised forward's 3 counted once; the weights read by 6 in
    # bf16, their 3 gradients written in f32
    assert cfg["model"]["kwargs"]["remat"]
    assert ops == 9 * 2 * 32768 * 2048 * 1536
    assert nbytes == (9 * 32768 * (2048 + 1536) * 2
                      + 4 * 8 * 2048 * 1536 * (6 * 2 + 3 * 4))
    ctx = {"trace": {"steps": 2, "kernels": {"fdtpu_gmm": 0.1}},
           "config": cfg, "peaks": PEAKS}
    fresh = Registry()
    monkeypatch.setattr(obs, "get_registry", lambda: fresh)
    for name in ("moe_gmm_roofline_pct", "moe_load_max_over_mean", "moe_dropped_pct"):
        assert reader(name).read(ctx) is None     # a program without the counters
    slots = fresh.counter("fdtpu_moe_slots_total", "", ("where",))
    slots.labels(where="held").inc(3 * 32768)
    fresh.counter("fdtpu_moe_dropped_total", "")
    balance = fresh.histogram("fdtpu_moe_load_max_over_mean", "", ("layer",),
                              buckets=(1.0, 2.0))
    for layer in range(4):
        for _ in range(3):
            balance.labels(layer=layer).observe(1.5)
    least = max(ops / 197e12, nbytes / 819e9)
    assert gmm.read(ctx) == pytest.approx(100 * least * 2 / 0.1)
    assert reader("moe_load_max_over_mean").read(ctx) == pytest.approx(1.5)
    assert reader("moe_dropped_pct").read(ctx) == 0.0
    # the kernel XLA ran until PR 36 is not this one
    assert gmm.read(dict(ctx, trace={"steps": 2, "kernels": {}})) is None
