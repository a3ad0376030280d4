"""The set-up's split and the loop's lead on the CPU: the eight readers
over `setup_split.py` on a timeline written by hand (the sum rule among
them), their silence on a program without the spans, their entries'
fields as the manifest has them, what each cell owes, and a traced run of
every cell at the tiny size."""

import json

import pytest

import test_chipbench_manifest as manifest_rules
from chipbench_tiny import ROOT, harness, run_tiny

from chipbench import steplog

M = harness.load_manifest()
NAMES = ["setup_model_init_s", "setup_warmup_s", "setup_first_step_s",
         "setup_trace_lower_s", "setup_cache_load_s", "setup_cache_misses",
         "setup_outside_program_s", "loop_ahead_steps"]
ENTRIES = [next(m for m in M["per_layer"] if m["name"] == n) for n in NAMES]
LISTED = [w["name"] for w in M["workloads"]]


def read(name, ctx):
    return harness.read_metric(ROOT, M, name, ctx)


def span(name, t0, t1, **args):
    ev = {"name": name, "ph": "X", "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
          "pid": 0, "tid": 1, "cat": "fdtpu"}
    if args:
        ev["args"] = args
    return ev


def compiled(t0, t1, fun, cache, parent, **args):
    return span("compile", t0, t1, fun_name=fun, cache=cache, parent=parent,
                **args)


def loop(t0, first, ahead, step=0.5):
    """The items of one call of `train()` from `t0` on: an `item` with
    its two phases and the `device` span that closes `step` later."""
    out = []
    for k, a in enumerate(ahead):
        j, t = first + k, t0 + 0.1 * k
        out += [span("item", t, t + 0.1, item=j, traced=False, parent="train"),
                span("data_wait", t, t + 0.01, item=j, parent="item"),
                span("dispatch", t + 0.01, t + 0.09, item=j, parent="item"),
                span("device", t0 + step * k + 0.09, t0 + step * (k + 1) + 0.09,
                     item=j, parent="dispatch", ahead=a)]
    return out


def a_run():
    """A process whose run began at 100 s on the ring's clock and whose
    window began at 147 s, after a run that ended before it."""
    before = [
        span("prepare", 10, 30), span("model_init", 11, 29, parent="prepare"),
        span("train", 31, 35, start_item=0), span("train", 36, 60, start_item=3),
        compiled(61, 90, "jit(reference)", "miss", None)]
    setup = [
        span("prepare", 104, 134),
        span("cache_enable", 104, 104.25, parent="prepare"),
        span("model_init", 104.25, 124.25, parent="prepare"),
        span("trace", 105, 106, fun_name="add", parent="model_init"),
        span("lower", 106, 107.5, fun_name="jit(add)", parent="model_init"),
        # a trace from inside a lowering is covered once
        span("trace", 106.5, 107, fun_name="mul", parent="model_init"),
        compiled(107.5, 109.5, "jit(add)", "hit", "model_init", load_s=1.75),
        compiled(110, 118, "jit(_normal)", "miss", "model_init"),
        compiled(119, 120, "jit(pallas_call)", "off", "model_init"),
        span("step_build", 124.25, 124.5, parent="prepare"),
        span("model_init", 124.5, 125.5, parent="prepare"),
        span("warmup", 125.5, 126, parent="prepare"),
        span("warmup", 126, 134, parent="prepare"),
        span("trace", 126, 128, fun_name="step", parent="warmup"),
        span("lower", 128, 129, fun_name="jit(step)", parent="warmup"),
        compiled(129, 132.5, "jit(step)", "hit", "warmup", load_s=3.25),
        # the benchmark's seeded weights, outside any span of the program
        compiled(135, 137, "jit(make_params)", "miss", None),
        span("train", 138, 144, start_item=0),
        *loop(138.5, 0, [0, 1, 2], step=1.0),
    ]
    window = [span("train", 147, 180, start_item=3),
              *loop(147.5, 3, [0, 1, 2, 3, 4, 4, 4, 4, 4, 4])]
    after = [compiled(181, 200, "jit(reference)", "miss", None)]
    ctx = {"setup_s": 47.0, "window": {"steps": 10},
           "traffic": {"steps_per_call": 1}}
    return before + setup + window + after, ctx


@pytest.mark.parametrize("name,want", [
    ("setup_model_init_s", 21.0),        # two spans, 20 s and 1 s
    ("setup_warmup_s", 8.5),             # the batch and the step
    ("setup_first_step_s", 1.59),        # 138 to item 0's completion
    ("setup_trace_lower_s", 5.5),        # 105 to 107.5 and 126 to 129
    ("setup_cache_load_s", 5.0),
    ("setup_cache_misses", 2),           # not the run before's, nor the reference's
    ("setup_outside_program_s", 11.0),   # 47 less 30 of prepare and 6 of train
    ("loop_ahead_steps", 3.0),           # (0 + 1 + 2 + 3 + 6 x 4) / 10
])
def test_a_reader_on_a_timeline_written_by_hand(monkeypatch, name, want):
    events, ctx = a_run()
    monkeypatch.setattr(steplog, "program_events", lambda: events)
    assert read(name, ctx) == pytest.approx(want, abs=1e-6)


def test_the_sum_rule_and_the_names_of_what_missed(monkeypatch, capsys):
    events, ctx = a_run()
    monkeypatch.setattr(steplog, "program_events", lambda: events)
    spans = sum(e["dur"] for e in events if e["name"] in ("prepare", "train")
                and 100e6 <= e["ts"] < 147e6) / 1e6
    assert read("setup_outside_program_s", ctx) + spans == pytest.approx(47.0)
    # a run whose first span began within the moment that lies between
    # the taking of setup_s and the window's start still holds it
    assert read("setup_outside_program_s", dict(ctx, setup_s=42.5)) + spans \
        == pytest.approx(42.5)
    # the three phases cover the program's share but for the state's
    # build and the loop's own time
    phases = sum(read(n, ctx) for n in NAMES[:3])
    assert phases / spans == pytest.approx((21.0 + 8.5 + 1.59) / 36.0)
    assert capsys.readouterr().out == ""
    read("setup_cache_misses", ctx)
    head, _, notes = capsys.readouterr().out.partition(" notes ")
    assert head == "chipbench" and json.loads(notes) == {"setup_cache_misses": [
        ["jit(_normal)", "model_init", 8.0], ["jit(make_params)", None, 2.0]]}
    # a session: the items it touched and those after them do not count
    traced = [dict(e, args=dict(e["args"], traced=True))
              if e["name"] == "item" and e["args"]["item"] == 8 else e
              for e in events]
    monkeypatch.setattr(steplog, "program_events", lambda: traced)
    assert read("loop_ahead_steps", ctx) == pytest.approx(10 / 5)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_spans_gives_nothing(monkeypatch, name):
    """The parent: its ring holds the loop's spans, no `train` or
    `prepare` and no `ahead`; and a program with no ring at all."""
    events, ctx = a_run()
    old = [dict(e, args={k: v for k, v in e["args"].items() if k != "ahead"})
           if "args" in e else e for e in events
           if e["name"] in ("item", "data_wait", "dispatch", "device")]
    for ring in (old, [], None):
        monkeypatch.setattr(steplog, "program_events", lambda: ring)
        assert read(name, ctx) is None
    # set-up spans the ring has dropped give no wrong number
    if name == "setup_outside_program_s":
        monkeypatch.setattr(steplog, "program_events", lambda: [
            e for e in events if e["name"] != "prepare"])
        assert read(name, ctx) is None


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_an_entry_keeps_to_the_manifests_rules(entry):
    manifest_rules.test_metric_entry(entry)
    assert entry["workloads"] == LISTED and entry["source"] == "program_span"
    assert entry["better"] == "lower"
    # the layer's name as the accepted entries spell it, and the metric
    # it moves
    assert (entry["layer"], entry["moves"]) == (
        ("trainer loop", "images_per_s_per_chip")
        if entry["name"] == "loop_ahead_steps" else ("cold start", "setup_s"))
    assert entry["layer"] in {m["layer"] for m in M["per_layer"]
                              if m["name"] not in NAMES}


@pytest.mark.parametrize("cell", LISTED)
def test_every_cell_owes_them(cell):
    owed = [m["name"] for m in harness.load_cell(cell).metrics["per_layer"]]
    assert [n for n in owed if n in NAMES] == NAMES


@pytest.mark.parametrize("cell", LISTED)
def test_a_traced_tiny_run_reads_all_eight(monkeypatch, cell):
    from fluxdistributed_tpu.obs import get_tracer

    seen, real = {}, harness.read_metric

    def spy(root, manifest, name, ctx):
        seen.update(setup_s=ctx["setup_s"])
        return real(root, manifest, name, ctx)

    monkeypatch.setattr(harness, "read_metric", spy)
    # a run of the benchmark is a process of its own; the ring of a test
    # process that ran other cells before may have no room left for this
    # run's set-up by the time its window ends
    get_tracer().clear()
    out = run_tiny(cell, trace=True)
    assert out["correct"], out["compared"]
    got = {n: out["metrics"][n] for n in NAMES}  # each gave a number
    assert [got[n]["unit"] for n in NAMES] == [e["unit"] for e in ENTRIES]
    value = {n: got[n]["value"] for n in NAMES}
    assert all(v >= 0 for v in value.values())
    # the sum rule on the run's own ring: what lies outside the program
    # and the program's spans make up setup_s, to the millisecond
    events = get_tracer().trace_events()
    window = max((e for e in events if e["name"] == "train"),
                 key=lambda e: e["ts"])
    assert window["args"] == {"start_item": 3}
    prepare = [e for e in events if e["name"] == "prepare"][-1]
    (first,) = [e for e in events if e["name"] == "train"
                and prepare["ts"] < e["ts"] < window["ts"]]
    assert first["args"] == {"start_item": 0}
    program_s = (prepare["dur"] + first["dur"]) / 1e6
    assert value["setup_outside_program_s"] + program_s == pytest.approx(
        seen["setup_s"], abs=1e-3)
    # the un-jitted init, the first step and (ResNet-50: warmup=True) the
    # warm-up are where the program's set-up goes
    assert value["setup_model_init_s"] > 0 and value["setup_first_step_s"] > 0
    assert (value["setup_warmup_s"] > 0) == (cell == "resnet50_b256_x1")
    phases = sum(value[n] for n in NAMES[:3])
    assert 0.5 * program_s < phases <= program_s + 1e-3
    assert 0 < value["setup_trace_lower_s"] < seen["setup_s"]
    # the tests run without a compile cache: nothing served, nothing missed
    assert value["setup_cache_load_s"] == 0 and value["setup_cache_misses"] == 0
    # a loop on the CPU runs a step or two ahead of its one device
    assert value["loop_ahead_steps"] <= 40
