"""The trace reducer on a trace written by hand."""

import pytest

from chipbench import trace


def test_union_and_subtract():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert trace.total(trace.union([(0, 10), (2, 3)])) == 10
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 4), (6, 8)], []) == [(0, 4), (6, 8)]
    assert trace.subtract([(0, 4)], [(0, 4)]) == []


def hand_trace():
    ms = 1_000_000
    step = lambda t0: [  # noqa: E731 - one 10 ms step starting at t0
        ("fusion.1", t0, t0 + 4 * ms),
        ("all-reduce-start.1", t0 + 4 * ms, t0 + 4 * ms + ms // 10),
        ("convolution.2", t0 + 4 * ms + ms // 10, t0 + 7 * ms),
        ("all-reduce-done.1", t0 + 7 * ms, t0 + 9 * ms),
    ]
    d0 = {"ops": step(0) + step(10 * ms) + step(20 * ms),
          "modules": [("jit_step", 0, 9 * ms), ("jit_step", 10 * ms, 19 * ms),
                      ("jit_step", 20 * ms, 29 * ms),
                      ("jit_norms", 29 * ms, 29 * ms + 1000)]}
    d1 = {"ops": [("fusion.1", 0, 29 * ms)], "modules": []}
    return {"devices": {0: d0, 1: d1}, "lines": {}}


def test_busy_idle_steps_and_exposed_all_reduce():
    r = trace.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.029)
    assert r["busy0_s"] == pytest.approx(0.027)
    assert r["busy_s"] == pytest.approx((0.027 + 0.029) / 2)
    # the first run may be cut and the last has no next: one whole step
    assert r["steps"] == 1 and r["step_module"] == "jit_step"
    assert r["steps_busy0_s"] == pytest.approx(0.009)
    assert r["has_all_reduce"]
    assert r["allreduce_exposed_s"] == pytest.approx(0.0021)
    assert r["device_ops"][0] == ["fusion", pytest.approx(0.012)]
    assert r["idle_gaps"] == [["before fusion", pytest.approx(0.002)]]


def test_a_hidden_all_reduce_is_not_exposed():
    t = hand_trace()
    t["devices"][0]["ops"].append(("fusion.9", 0, 29_000_000))
    assert trace.reduce(t)["allreduce_exposed_s"] == 0.0


def test_nothing_on_the_device_gives_nothing():
    assert trace.reduce({"devices": {}, "lines": {}}) is None
    assert trace.reduce({"devices": {0: {"ops": [], "modules": []}},
                         "lines": {}}) is None


@pytest.mark.parametrize("reader,ctx,want", [
    ("device_idle_pct", {"trace": None}, None),
    ("step_device_ms", {"trace": {"steps": 0, "steps_busy0_s": 1.0}}, None),
    ("allreduce_exposed_ms", {"trace": {"steps": 2, "has_all_reduce": False,
                                        "allreduce_exposed_s": 0.0}}, None),
    ("allreduce_exposed_ms", {"trace": {"steps": 2, "has_all_reduce": True,
                                        "allreduce_exposed_s": 0.004}}, 2.0),
    ("device_idle_pct", {"trace": {"window_s": 2.0, "busy_s": 1.5}}, 25.0),
    ("hbm_peak_gib", {"memory_peak_bytes": None}, None),
])
def test_a_reader_that_finds_nothing_returns_nothing(reader, ctx, want):
    from chipbench_tiny import ROOT, harness

    got = harness.read_metric(ROOT, harness.load_manifest(), reader, ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def test_names_are_cut_to_the_instruction_and_grouped_by_kind():
    long = "%convert_reduce_fusion.9 = (f32[512]{0:T(512)S(1)}) fusion(f32[512] %copy-done.186), kind=kOutput"
    assert trace.short_name(long) == "convert_reduce_fusion.9"
    assert trace.kind_of("convert_reduce_fusion.9") == "convert_reduce_fusion"
    assert trace.kind_of("all-reduce-start.12.1") == "all-reduce-start"
    assert trace.kind_of("fusion") == "fusion"


MS = 1_000_000
#: a step's work by kind, ms: ten kinds of 8.9 and the flash dQ kernel's
#: 1 at the eleventh place, then 10 ms of nothing
STEP = [(f"{kind}.{k}", 8.9) for k, kind in enumerate((
    "fusion", "copy", "convolution", "reshape", "broadcast", "transpose",
    "multiply_subtract_fusion", "multiply_reduce_fusion", "add_fusion",
    "select"))] + [("fdtpu_flash_dq.3", 1.0)]


def sliced_trace(step=STEP, cut=(70, 320), lengths=(100.0,) * 4):
    """Runs of the step program of the given lengths, each opening with
    the step's 90 ms of work, traced from ``cut[0]`` to ``cut[1]`` ms as
    the profiler records a session: the ops and the runs clipped to it
    (by default the first and the last run then read 30 and 20 ms)."""
    ops, modules, t0 = [], [], 0.0
    for length in lengths:
        modules.append(("jit_step(123)", t0, t0 + length))
        t = t0
        for name, ms in step:
            ops.append((name, t, t + ms))
            t += ms
        t0 += length
    clip = lambda evs: [  # noqa: E731
        (n, max(s, cut[0]) * MS, min(e, cut[1]) * MS)
        for n, s, e in evs if e > cut[0] and s < cut[1]]
    modules = clip(modules) + [("jit_norms", 311 * MS, 311 * MS + 1000)]
    return {"devices": {0: {"ops": clip(ops), "modules": modules}}, "lines": {}}


def test_only_whole_steps_count():
    r = trace.reduce(sliced_trace())
    # of the runs 30 (cut), 100, 100, 20 (cut) ms: two whole steps
    assert r["steps"] == 2 and r["step_module"] == "jit_step(123)"
    assert r["steps_busy0_s"] == pytest.approx(0.18)
    # the slice's own busy time and length are the whole slice's
    assert r["busy0_s"] == pytest.approx(0.22)
    assert r["window_s"] == pytest.approx(0.25)
    from chipbench_tiny import ROOT, harness

    got = harness.read_metric(ROOT, harness.load_manifest(), "step_device_ms",
                              {"trace": r})
    assert got == pytest.approx(90.0)


@pytest.mark.parametrize("cut,steps", [((70, 320), 1), ((50, 600), 4),
                                       ((100, 645), 3), ((0, 645), 4)])
def test_whole_steps_of_unequal_lengths_are_exact_wherever_the_cut_falls(
        cut, steps):
    """Steps of 100, 95, 140, 90, 120 and 100 ms: each whole one holds
    its 90 ms of work and 1 ms of the dQ kernel, so a share and a step's
    time read the same wherever the session starts and stops."""
    r = trace.reduce(sliced_trace(cut=cut,
                                  lengths=(100, 95, 140, 90, 120, 100)))
    assert r["steps"] == steps
    assert r["kernels"]["fdtpu_flash_dq"] == pytest.approx(0.001 * steps)
    assert r["steps_busy0_s"] == pytest.approx(0.090 * steps)


def test_a_kernel_eleventh_by_time_is_still_read():
    r = trace.reduce(sliced_trace())
    assert len(r["device_ops"]) == 10
    assert "fdtpu_flash_dq" not in dict(r["device_ops"])
    # the whole steps' dQ ran at 189-190 and 289-290 ms; the cut first
    # step's, at 89-90 ms inside the slice, is not counted
    assert r["kernels"] == {"fdtpu_flash_dq": pytest.approx(0.002)}
    from chipbench_tiny import ROOT, harness

    m = harness.load_manifest()
    cfg = harness.load_cell("glm47_flash_t4096_b4_x1").config
    attn = harness.load_module(harness.find_file(ROOT, m, "metrics",
                                                 "attn_roofline_pct.py"))
    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    ctx = {"trace": r, "config": cfg, "traffic": {"global_batch": 4},
           "chips": 1, "peaks": peaks}
    least = attn.least_seconds(attn.step_work(cfg, 4)["fdtpu_flash_dq"], peaks)
    assert attn.read(ctx) == pytest.approx(100 * least * 2 / 0.002)


@pytest.mark.parametrize("kernel,reads", [("fdtpu_gmm", True),
                                          ("ragged-dot-none", False)])
def test_the_grouped_products_share_reads_fdtpu_gmm(monkeypatch, kernel, reads):
    from chipbench_tiny import ROOT, harness

    step = STEP[:-1] + [(kernel + ".7", 1.0)]
    r = trace.reduce(sliced_trace(step))
    m = harness.load_manifest()
    gmm = harness.load_module(harness.find_file(ROOT, m, "metrics",
                                                "moe_gmm_roofline_pct.py"))
    monkeypatch.setattr(gmm, "rows_per_step", lambda: 32768.0)
    cfg = harness.load_cell("glm47_flash_t4096_b4_x1").config
    peaks = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    got = gmm.read({"trace": r, "config": cfg, "peaks": peaks})
    if not reads:
        assert got is None
        return
    ops, nbytes = gmm.step_work(cfg, 32768.0)
    least = max(ops / 197e12, nbytes / 819e9)
    assert got == pytest.approx(100 * least * 2 / 0.002)
