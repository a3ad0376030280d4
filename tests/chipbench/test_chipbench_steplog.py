"""The reduction from the program's step timeline to the five per-layer
numbers, on events written by hand; the readers against a program with
no process-wide tracer; and a traced run of a cell at a tiny size."""

import random

import pytest

from chipbench_tiny import ROOT, harness, run_tiny

from chipbench import steplog

M = harness.load_manifest()
NEW = ["starved_data_wait_pct", "starved_dispatch_pct", "starved_other_pct",
       "assemble_ms", "step_interval_p95_ms"]


def span(name, start_ms, end_ms, item=None, **args):
    if item is not None:
        args["item"] = item
    return {"name": name, "ph": "X", "ts": start_ms * 1e3,
            "dur": (end_ms - start_ms) * 1e3, "pid": 0, "tid": 1, "args": args}


def loop(items, wait_ms, dispatch_ms, done_after_ms, period_ms=100.0,
         assemble_ms=30.0, traced=(), first=0):
    """``items`` loader items, one a period.  Item ``j`` waits for data,
    then dispatches; its step completes ``done_after_ms`` after the
    item's start.  The device span is built as the watcher builds it."""
    events, last_done = [], 0.0
    for k in range(items):
        j, t = first + k, k * period_ms
        d0, d1 = t + wait_ms, t + wait_ms + dispatch_ms
        done = t + done_after_ms
        events += [
            span("assemble", t - 250.0, t - 250.0 + assemble_ms, j, parent="item"),
            span("h2d", t - 250.0 + assemble_ms, t - 200.0, j, parent="item"),
            span("data_wait", t, d0, j, parent="item"),
            span("dispatch", d0, d1, j, parent="item"),
            span("item", t, t + period_ms, j, opt_step=j, traced=j in traced),
            span("device", max(d1, last_done), done, j, parent="dispatch"),
        ]
        last_done = done
    return events


def test_an_item_that_starves_in_data_wait():
    # the step is done 95 ms in; the next one is in flight 45 ms into the
    # next item, after 40 ms of waiting for data and 5 ms of dispatch
    r = steplog.reduce(loop(25, 40.0, 5.0, 95.0), 24)
    assert r["items"] == 24
    assert r["starved_data_wait_pct"] == pytest.approx(40.0)
    assert r["starved_dispatch_pct"] == pytest.approx(5.0)
    assert r["starved_other_pct"] == pytest.approx(5.0)
    assert r["assemble_ms"] == pytest.approx(30.0)
    assert r["step_intervals"] == 23
    assert r["step_interval_p95_ms"] == pytest.approx(100.0)


def test_an_item_that_starves_in_dispatch():
    r = steplog.reduce(loop(25, 1.0, 59.0, 99.0), 24)
    assert r["starved_data_wait_pct"] == pytest.approx(1.0)
    assert r["starved_dispatch_pct"] == pytest.approx(59.0)
    assert r["starved_other_pct"] == pytest.approx(1.0)


def test_back_pressure_starves_nowhere():
    # the dispatch blocks 79 ms of every 100, but the step before is
    # still on the device when it returns: two in flight, nothing starved
    r = steplog.reduce(loop(25, 1.0, 79.0, 190.0), 24)
    for name in NEW[:3]:
        assert r[name] == pytest.approx(0.0, abs=1e-9)
    # without the item before the window the first 80 ms count: the device
    # holds nothing until the first dispatch returns
    r = steplog.reduce(loop(24, 1.0, 79.0, 190.0), 24)
    assert r["starved_data_wait_pct"] == pytest.approx(100 * 1.0 / 2400)
    assert r["starved_dispatch_pct"] == pytest.approx(100 * 79.0 / 2400)
    assert r["starved_other_pct"] == pytest.approx(0.0, abs=1e-9)


def test_worker_spans_out_of_order_and_an_older_run_under_the_same_ids():
    events = loop(25, 40.0, 5.0, 95.0)
    want = steplog.reduce(events, 24)
    # an older run in the ring used the same ids with other timings: the
    # later span of an id counts
    older = [dict(e, ts=e["ts"] - 1e7) for e in loop(25, 1.0, 1.0, 60.0,
                                                      assemble_ms=7.0)]
    random.Random(3).shuffle(events)
    assert steplog.reduce(older + events, 24) == pytest.approx(want)
    # a batch assembled late and slowly moves the mean, nothing else
    slow = [e for e in events
            if not (e["name"] == "assemble" and e["args"]["item"] == 9)]
    slow.append(span("assemble", 600.0, 870.0, 9, parent="item"))
    got = steplog.reduce(slow, 24)
    assert got["assemble_ms"] == pytest.approx(30.0 + 240.0 / 24)
    assert got["starved_data_wait_pct"] == pytest.approx(40.0)


def test_traced_items_and_all_after_them_are_left_out():
    plain = steplog.reduce(loop(25, 40.0, 5.0, 95.0), 24)
    traced = set(range(25, 29))
    events = loop(40, 40.0, 5.0, 95.0, traced=traced)
    for e in events:
        # under the profiler items dispatch far later, and those after
        # the session find a full prefetch buffer and never wait: neither
        # may reach the numbers
        if e["args"]["item"] in traced and e["name"] == "dispatch":
            e["dur"] += 30e3
        if e["args"]["item"] > 28 and e["name"] == "data_wait":
            e["dur"] = 1.0
    r = steplog.reduce(events, 39)
    assert r["items"] == 24  # items 1 to 24 of the last 39
    for name in NEW:
        assert r[name] == pytest.approx(plain[name])
    assert r["step_intervals"] == 23
    # a session before the window's first item leaves nothing to read
    assert steplog.reduce(loop(40, 40.0, 5.0, 95.0, traced={0}), 40) is None
    # a step done 25 ms later, at 2,420 ms, covers 20 ms of the next
    # item's wait for data
    late = [dict(e, dur=e["dur"] + 25e3) if e["name"] == "device"
            and e["args"]["item"] == 23 else e for e in events]
    assert steplog.reduce(late, 39)["starved_data_wait_pct"] == pytest.approx(
        100 * (40.0 * 24 - 20.0) / 2400)


def test_step_interval_p95_is_the_nearest_rank_over_following_items():
    events = loop(41, 1.0, 1.0, 50.0)
    for e in events:
        if e["name"] == "device" and e["args"]["item"] in (20, 30):
            e["dur"] += 25e3  # two late completions of 40 intervals
    r = steplog.reduce(events, 41)
    assert r["step_intervals"] == 40
    assert r["step_interval_p50_ms"] == pytest.approx(100.0)
    assert r["step_interval_p95_ms"] == pytest.approx(100.0)  # rank 38 of 40
    for e in events:
        if e["name"] == "device" and e["args"]["item"] == 10:
            e["dur"] += 25e3  # a third: rank 38 is now late
    assert steplog.reduce(events, 41)["step_interval_p95_ms"] == pytest.approx(125.0)


@pytest.mark.parametrize("items,expect", [(19, False), (20, True)])
def test_fewer_than_twenty_items_give_no_number(items, expect):
    r = steplog.reduce(loop(items, 40.0, 5.0, 95.0), 500)
    assert (r is not None) == expect
    assert steplog.reduce(loop(40, 40.0, 5.0, 95.0), 0) is None
    # an item the loop opened and never dispatched (its last look at the
    # loader, a stop at the boundary) is no item of the window
    events = loop(19, 40.0, 5.0, 95.0) + [
        span("data_wait", 1900.0, 1901.0, 19, parent="item"),
        span("item", 1900.0, 1902.0, 19, opt_step=19, traced=False)]
    assert steplog.reduce(events, 20) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_for_a_program_without_the_tracer(name, monkeypatch):
    from fluxdistributed_tpu import obs

    ctx = {"window": {"steps": 100}, "traffic": {"steps_per_call": 1}}
    obs.get_tracer().clear()
    assert harness.read_metric(ROOT, M, name, ctx) is None  # an empty ring
    monkeypatch.delattr(obs, "get_tracer")
    assert steplog.program_events() is None
    assert harness.read_metric(ROOT, M, name, ctx) is None


def test_traced_tiny_run_reports_the_five_beside_the_old_ones():
    import time

    import jax

    from chipbench_tiny import PEAKS, tiny_cell

    # the slice starts late enough for twenty items to lie before it,
    # also on a machine that runs five other test workers
    cell = tiny_cell("resnet50_b256_x1")
    cell.traffic = dict(cell.traffic, trace_after_s=1.5, trace_for_s=0.3)
    out = harness.run_cell(cell, 5, 2.5, True, t_process=time.perf_counter(),
                           devices=jax.devices()[:1], peaks=PEAKS)
    assert set(NEW) | {"compile_s", "window_compiles", "dispatch_ms",
                       "h2d_ms", "data_wait_pct"} <= set(out["metrics"])
    shares = [out["metrics"][n]["value"] for n in NEW[:3]]
    assert all(0.0 <= s <= 100.0 for s in shares) and sum(shares) <= 100.0
    assert out["metrics"]["assemble_ms"]["value"] > 0
    assert out["metrics"]["step_interval_p95_ms"]["value"] > 0
    # the end-to-end run reads none of them
    assert set(run_tiny("resnet50_b256_x1")["metrics"]) == {
        "images_per_s_per_chip", "setup_s"}
