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
    d0 = {"ops": step(0) + step(10 * ms),
          "modules": [("jit_step", 0, 9 * ms), ("jit_step", 10 * ms, 19 * ms),
                      ("jit_norms", 19 * ms, 19 * ms + 1000)]}
    d1 = {"ops": [("fusion.1", 0, 19 * ms)], "modules": []}
    return {"devices": {0: d0, 1: d1}, "lines": {}}


def test_busy_idle_steps_and_exposed_all_reduce():
    r = trace.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.019)
    assert r["busy0_s"] == pytest.approx(0.018)
    assert r["busy_s"] == pytest.approx((0.018 + 0.019) / 2)
    assert r["steps"] == 2 and r["step_module"] == "jit_step"
    assert r["has_all_reduce"]
    assert r["allreduce_exposed_s"] == pytest.approx(2 * 0.0021)
    assert r["device_ops"][0] == ["fusion", pytest.approx(0.008)]
    assert r["idle_gaps"] == [["before fusion", pytest.approx(0.001)]]


def test_a_hidden_all_reduce_is_not_exposed():
    t = hand_trace()
    t["devices"][0]["ops"].append(("fusion.9", 0, 19_000_000))
    assert trace.reduce(t)["allreduce_exposed_s"] == 0.0


def test_nothing_on_the_device_gives_nothing():
    assert trace.reduce({"devices": {}, "lines": {}}) is None
    assert trace.reduce({"devices": {0: {"ops": [], "modules": []}},
                         "lines": {}}) is None


@pytest.mark.parametrize("reader,ctx,want", [
    ("device_idle_pct", {"trace": None}, None),
    ("step_device_ms", {"trace": {"steps": 0, "busy0_s": 1.0}}, None),
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
