"""Unified observability layer (fluxdistributed_tpu.obs).

Covers the four obs modules at unit level — Prometheus exposition
format (label escaping, counter monotonicity, histogram cumulation),
span nesting + Chrome/Perfetto trace-event validity, watchdog stall
detection, jax.monitoring recompile flagging — plus the serve-metrics
parity contract: every pre-registry ``fdtpu_serve_*`` series name and
the ``Scheduler.metrics()`` dict keys survive the registry migration
byte-identically.
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from fluxdistributed_tpu.obs import (
    JsonlSink,
    Observation,
    Registry,
    SpanTracer,
    StepWatchdog,
    current_span,
    get_registry,
    jaxmon,
    start_metrics_server,
)


# ---------------------------------------------------------------------------
# metrics: registry + exposition format
# ---------------------------------------------------------------------------

def test_counter_monotonic():
    r = Registry()
    c = r.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError, match="monotonic"):
        c.inc(-1)
    assert c.value() == 3.5


def test_gauge_set_inc_dec_and_callback():
    r = Registry()
    g = r.gauge("g", "a gauge")
    g.set(10)
    g.dec(3)
    assert g.value() == 7
    cb = r.gauge("g_cb", "computed at scrape time")
    cb.set_function(lambda: 42)
    assert cb.value() == 42
    # a dead callback must not kill the scrape — it reads NaN
    cb.set_function(lambda: 1 / 0)
    text = r.prometheus_text()
    assert "g_cb nan" in text.lower()


def test_get_or_create_and_conflicts():
    r = Registry()
    a = r.counter("x_total", "first")
    assert r.counter("x_total", "again") is a  # idempotent re-register
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x_total")  # kind conflict
    with pytest.raises(ValueError, match="already registered"):
        r.counter("x_total", labelnames=("k",))  # label conflict


def test_label_escaping_and_exposition_lines():
    r = Registry()
    c = r.counter("esc_total", 'tricky "help"', labelnames=("path",))
    c.labels(path='a"b\\c\nd').inc(2)
    text = r.prometheus_text()
    assert "# TYPE esc_total counter" in text
    # backslash, quote and newline must be escaped inside the quotes
    assert 'esc_total{path="a\\"b\\\\c\\nd"} 2' in text
    # unlabeled metrics expose as bare `name value`
    g = r.gauge("plain", "no labels")
    g.set(1.5)
    assert "\nplain 1.5" in r.prometheus_text()


def test_labels_validation():
    r = Registry()
    c = r.counter("l_total", "", labelnames=("a", "b"))
    with pytest.raises(ValueError, match="label values"):
        c.labels("only-one")
    with pytest.raises(ValueError, match="has labels"):
        c.labels(a="x", wrong="y")
    with pytest.raises(ValueError, match="call .labels"):
        c.inc()  # labeled metric has no default cell
    c.labels(a="x", b="y").inc()
    assert c.value("x", "y") == 1


def test_histogram_cumulative_buckets_sum_count():
    r = Registry()
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 99.0):
        h.observe(v)
    text = r.prometheus_text()
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text  # cumulative
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert "# TYPE lat_seconds histogram" in text
    assert h.cell_sum() == pytest.approx(99.55)
    with h.time():
        pass
    assert h.cell_count() == 4


def test_snapshot_and_jsonl_sink(tmp_path):
    r = Registry()
    r.counter("s_total", "").inc(2)
    r.histogram("h_seconds", "").observe(0.25)
    snap = r.snapshot()
    assert snap["s_total"] == 2
    assert snap["h_seconds_count"] == 1
    path = tmp_path / "m.jsonl"
    sink = JsonlSink(str(path), r)
    sink.write(step=5)
    r.counter("s_total", "").inc()
    sink.write(step=6, final=True)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["step"] == 5 and lines[0]["metrics"]["s_total"] == 2
    assert lines[1]["final"] and lines[1]["metrics"]["s_total"] == 3


def test_registry_value_reader():
    r = Registry()
    assert r.value("missing", default=-1) == -1
    r.counter("v_total", "").inc(4)
    assert r.value("v_total") == 4


# ---------------------------------------------------------------------------
# spans: nesting + Chrome trace-event JSON
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_export(tmp_path):
    t = SpanTracer()
    assert current_span() is None
    with t.span("step", idx=3):
        assert current_span() == "step"
        with t.span("dispatch"):
            assert current_span() == "dispatch"
            time.sleep(0.002)
        assert current_span() == "step"
    assert current_span() is None

    path = tmp_path / "trace.json"
    n = t.export_chrome_trace(str(path))
    assert n == 2
    doc = json.loads(path.read_text())  # valid JSON by construction
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"step", "dispatch"}
    for e in evs:
        # the trace-event schema fields Perfetto/chrome://tracing need
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert "pid" in e and "tid" in e
    outer = next(e for e in evs if e["name"] == "step")
    inner = next(e for e in evs if e["name"] == "dispatch")
    # proper nesting: the inner complete-event lies within the outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"idx": 3}
    # a child names the span that caused it
    assert inner["args"] == {"parent": "step"}


def test_one_bracket_feeds_span_histogram_and_flight_split():
    """What replaced ``SpanTracer(histogram=)`` and the tracer-less
    bracket: the trainer's phase clock opens the span in the process
    tracer and observes the phase histogram from the SAME bracket, on
    the exception path too."""
    from fluxdistributed_tpu.obs import get_tracer
    from fluxdistributed_tpu.train.trainer import _PhaseClock

    r = Registry()
    phases = _PhaseClock(Observation(registry=r))
    assert phases.tracer is get_tracer()
    get_tracer().clear()
    phases.begin_item(7, opt_step=7)
    with phases("fit"):
        assert current_span() == "fit"
    with pytest.raises(ZeroDivisionError):
        with phases("fit"):
            1 / 0
    phases.end_item()
    assert current_span() is None
    h = r.get("fdtpu_train_phase_seconds")
    assert h.labels(phase="fit").count == 2
    assert set(phases.take()) == {"fit"} and phases.take() == {}
    evs = get_tracer().trace_events()
    assert [e["name"] for e in evs] == ["fit", "fit", "item"]
    assert all(e["args"]["item"] == 7 for e in evs)
    assert evs[0]["args"]["parent"] == "item"
    assert evs[2]["args"]["traced"] is False


def test_span_ring_bounds_memory():
    t = SpanTracer(max_events=4)
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 4
    assert t.dropped == 6
    assert [e["name"] for e in t.trace_events()] == ["s6", "s7", "s8", "s9"]


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_quiet_on_cadence_fires_on_stall():
    r = Registry()
    fired = []
    w = StepWatchdog(factor=3.0, min_interval=0.05, warmup=2,
                     registry=r, on_stall=lambda e, th: fired.append((e, th)))
    for _ in range(8):
        w.beat()
        time.sleep(0.01)
    assert w.poll() is False  # steady cadence: quiet
    assert r.value("fdtpu_watchdog_stalls_total") == 0
    time.sleep(0.3)  # ~30x the median interval > threshold
    assert w.poll() is True
    assert w.poll() is False  # one warning per stall episode
    assert fired and fired[0][0] > fired[0][1]
    assert r.value("fdtpu_watchdog_stalls_total") == 1
    assert r.value("fdtpu_watchdog_stalled") == 1
    w.beat()  # recovery re-arms and clears the stalled gauge
    assert r.value("fdtpu_watchdog_stalled") == 0
    assert w.poll() is False


def test_watchdog_pause_exempts_known_long_work():
    """A checkpoint/eval longer than the threshold must NOT read as a
    stall (train() wraps those phases in pause()), and the paused span
    must not pollute the rolling median."""
    r = Registry()
    w = StepWatchdog(factor=3.0, min_interval=0.02, warmup=2, registry=r)
    for _ in range(6):
        w.beat()
        time.sleep(0.005)
    med_before = w.threshold()
    with w.pause():
        time.sleep(0.2)  # a long checkpoint: way past the threshold
        assert w.poll() is False  # suspended while paused
    assert w.poll() is False  # interval restarted on exit — no stall
    assert r.value("fdtpu_watchdog_stalls_total") == 0
    w.beat()
    assert w.threshold() == pytest.approx(med_before, rel=0.9)


def test_watchdog_escalates_persistent_stall():
    """A stall that persists escalate_after further threshold windows
    fires ONE escalation (counter + abort callback) — today's
    warn-once would otherwise sit silent on a permanently wedged loop."""
    r = Registry()
    escalated = []
    w = StepWatchdog(factor=2.0, min_interval=0.01, warmup=2, registry=r,
                     escalate_after=2,
                     on_escalate=lambda e, th: escalated.append((e, th)))
    for i in range(6):
        w.beat()
    # drive poll() with synthetic clocks: the threshold is
    # min_interval-floored, escalation sits at (1 + 2) x threshold
    thr = w.threshold()
    base = time.monotonic()
    assert w.poll(now=base + 2 * thr) is True  # the stall fires first
    assert r.value("fdtpu_watchdog_stalls_total") >= 1
    assert r.value("fdtpu_watchdog_escalations_total") == 0
    # inside the escalation window: nothing yet
    w.poll(now=base + 2.5 * thr)
    assert escalated == []
    # past (1 + escalate_after) x threshold: exactly one escalation
    w.poll(now=base + 3.5 * thr)
    w.poll(now=base + 5.0 * thr)
    assert len(escalated) == 1
    assert r.value("fdtpu_watchdog_escalations_total") == 1
    # a beat re-arms the whole episode machinery
    w.beat()
    assert r.value("fdtpu_watchdog_stalled") == 0
    w.poll(now=base + 100.0)
    w.poll(now=base + 200.0)
    assert r.value("fdtpu_watchdog_escalations_total") == 2


def test_watchdog_escalation_disabled_by_default():
    r = Registry()
    w = StepWatchdog(factor=2.0, min_interval=0.01, warmup=2, registry=r)
    for _ in range(6):
        w.beat()
    thr = w.threshold()
    w.poll(now=time.monotonic() + thr * 2)
    w.poll(now=time.monotonic() + thr * 1000)
    assert r.value("fdtpu_watchdog_stalls_total") == 1
    assert r.value("fdtpu_watchdog_escalations_total") == 0
    with pytest.raises(ValueError, match="escalate_after"):
        StepWatchdog(escalate_after=-1, registry=r)


def test_watchdog_pause_does_not_collapse_median():
    """The beat that ends a pause-containing iteration measures only
    the post-pause remainder; recording it would drive the rolling
    median toward zero and floor the threshold (false stalls on every
    slow-but-healthy step when eval runs every iteration)."""
    w = StepWatchdog(factor=3.0, min_interval=0.0, warmup=2,
                     registry=Registry())
    for _ in range(4):
        w.beat()
        time.sleep(0.02)
    med = statistics_median(w)
    for _ in range(6):  # eval_every=1 shape: pause inside EVERY iteration
        with w.pause():
            pass
        w.beat()  # immediately after pause exit: near-zero remainder
        time.sleep(0.02)
    assert statistics_median(w) == pytest.approx(med, rel=0.9), (
        "post-pause beats polluted the rolling median"
    )


def statistics_median(w: StepWatchdog) -> float:
    import statistics

    return statistics.median(w._intervals)


def test_jsonl_sink_writes_valid_json_for_nan_gauges(tmp_path):
    """A dead callback gauge reads NaN; the sink must still emit strict
    JSON (bare NaN tokens break jq — the file's whole purpose)."""
    r = Registry()
    r.gauge("dead", "").set_function(lambda: 1 / 0)
    r.counter("ok_total", "").inc()
    path = tmp_path / "m.jsonl"
    JsonlSink(str(path), r).write(step=1)
    rec = json.loads(path.read_text(), parse_constant=lambda c: pytest.fail(
        f"non-strict JSON constant {c} in sink output"))
    assert rec["metrics"]["dead"] is None
    assert rec["metrics"]["ok_total"] == 1


def test_watchdog_unarmed_during_warmup():
    w = StepWatchdog(factor=2.0, min_interval=0.0, warmup=5, registry=Registry())
    w.beat()
    w.beat()
    assert w.threshold() is None
    assert w.poll() is False  # never fires before the warmup beats


def test_watchdog_stall_names_innermost_active_phase(capsys):
    """A stall episode must say WHERE the loop wedged: the warning names
    the innermost active span/phase (registered cross-thread — the
    watchdog polls from its own thread) and last_where keeps it for
    callbacks."""
    from fluxdistributed_tpu.obs import get_tracer
    from fluxdistributed_tpu.obs.spans import innermost_active

    r = Registry()
    w = StepWatchdog(factor=2.0, min_interval=0.01, warmup=2, registry=r)
    for _ in range(5):
        w.beat()
        time.sleep(0.005)
    entered, release = threading.Event(), threading.Event()

    def wedged_loop():  # the "hung dispatch" on the loop's own thread
        with get_tracer().span("dispatch"):
            entered.set()
            release.wait(5)

    t = threading.Thread(target=wedged_loop)
    t.start()
    try:
        assert entered.wait(2)
        assert innermost_active() == "dispatch"
        time.sleep(0.06)  # well past factor x median
        assert w.poll() is True
        assert w.last_where == "dispatch"
        err = capsys.readouterr().err
        assert "STALL" in err and "'dispatch'" in err
    finally:
        release.set()
        t.join()
    assert innermost_active() is None  # registry cleaned up on exit


def test_span_tracer_registers_active_span():
    from fluxdistributed_tpu.obs.spans import innermost_active

    t = SpanTracer()
    assert innermost_active() is None
    with t.span("step"):
        with t.span("h2d"):
            assert innermost_active() == "h2d"
        assert innermost_active() == "step"
    assert innermost_active() is None


def test_watchdog_thread_and_oom_fold_in():
    r = Registry()
    fired = threading.Event()
    w = StepWatchdog(factor=2.5, min_interval=0.02, warmup=2,
                     check_every=0.02, registry=r,
                     on_stall=lambda e, th: fired.set())
    with w:
        for _ in range(6):
            w.beat()
            time.sleep(0.01)
        w.note_skip(2)  # OOM skip: heartbeat + counted lost work
        assert fired.wait(2.0), "watchdog thread never fired on a stall"
    assert r.value("fdtpu_train_oom_skipped_total") == 2


# ---------------------------------------------------------------------------
# jaxmon: compile counters + steady-state recompile detector
# ---------------------------------------------------------------------------

def test_jaxmon_counts_compiles_and_flags_steady_recompiles():
    import jax
    import jax.numpy as jnp

    jaxmon.install()
    reg = get_registry()

    f = jax.jit(lambda x: x * 2 + 1)
    before = reg.value("fdtpu_jax_compiles_total")
    f(jnp.ones(3))  # warmup compile
    assert reg.value("fdtpu_jax_compiles_total") > before
    assert reg.value("fdtpu_jax_compile_seconds_total") > 0

    steady_before = reg.value("fdtpu_jax_steady_recompiles_total")
    warnings = []
    jaxmon.install(warn=warnings.append)
    with jaxmon.steady_state():
        f(jnp.ones(3))  # cache hit: not a recompile
        assert reg.value("fdtpu_jax_steady_recompiles_total") == steady_before
        f(jnp.ones(5))  # deliberate shape change -> recompile, flagged
    assert reg.value("fdtpu_jax_steady_recompiles_total") > steady_before
    assert any("RECOMPILE" in w for w in warnings)
    # outside the block the flag is restored: compiles count but don't flag
    after = reg.value("fdtpu_jax_steady_recompiles_total")
    f(jnp.ones(7))
    assert reg.value("fdtpu_jax_steady_recompiles_total") == after


# ---------------------------------------------------------------------------
# the set-up on the timeline: compiles by program and cache outcome, traces
# and lowerings, prepare_training's phases, the loop's lead over the device
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """A persistent compile cache of this test's own; the process's
    cache config is left as it was found."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from fluxdistributed_tpu import compilation

    monkeypatch.delenv(compilation.CACHE_DIR_ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    yield compilation.enable_persistent_cache(str(tmp_path / "cache"))
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()
    compilation._cache_dir = None


def _probe_program(k):
    """A program no other test compiles, under a name of its own."""
    def fdtpu_obs_probe(x):
        return x * k + 4099
    return fdtpu_obs_probe


def _spans_of(events, fun):
    return {e["name"]: e for e in events
            if fun in (e.get("args") or {}).get("fun_name", "")}


def test_compile_span_names_the_program_and_what_the_cache_did(cache_dir):
    import jax
    import jax.numpy as jnp

    from fluxdistributed_tpu.obs import get_tracer

    tracer, reg = get_tracer(), get_registry()
    loaded = reg.value("fdtpu_jax_cache_load_seconds_total")
    x = jnp.ones(11)
    rows = []
    for _ in range(2):
        tracer.clear()
        # a fresh lowering compiles anew: jax's in-memory caches hold
        # nothing of it, so the second meets the persistent cache
        with tracer.span("warmup"):
            jax.jit(_probe_program(3)).lower(x).compile()
        rows.append(_spans_of(tracer.trace_events(), "fdtpu_obs_probe"))
    miss, hit = (r["compile"]["args"] for r in rows)
    assert miss == {"parent": "warmup", "cache": "miss",
                    "fun_name": "jit(fdtpu_obs_probe)"}
    assert hit["cache"] == "hit" and hit["fun_name"] == miss["fun_name"]
    # a hit's span is the fetch and the load, and says how long they took
    assert 0 < hit["load_s"] <= rows[1]["compile"]["dur"] / 1e6 + 1e-3
    assert reg.value("fdtpu_jax_cache_load_seconds_total") == pytest.approx(
        loaded + hit["load_s"])
    # the next compile starts from nothing: no outcome is carried over
    tracer.clear()
    jax.jit(_probe_program(5)).lower(x).compile()
    again = _spans_of(tracer.trace_events(), "fdtpu_obs_probe")["compile"]
    assert again["args"] == {"cache": "miss",
                             "fun_name": "jit(fdtpu_obs_probe)"}


def test_compile_span_says_off_where_no_cache_served_or_kept_it(cache_dir):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from fluxdistributed_tpu.obs import get_tracer

    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
    get_tracer().clear()
    jax.jit(_probe_program(7)).lower(jnp.ones(13)).compile()
    got = _spans_of(get_tracer().trace_events(), "fdtpu_obs_probe")
    assert got["compile"]["args"] == {"cache": "off",
                                      "fun_name": "jit(fdtpu_obs_probe)"}


def test_trace_and_lower_spans_carry_the_program_and_their_parent():
    import jax
    import jax.numpy as jnp

    from fluxdistributed_tpu.obs import get_tracer

    jaxmon.install()
    tracer, reg = get_tracer(), get_registry()
    inner = jax.jit(lambda x: x - 8191)

    def fdtpu_obs_outer(x):
        return inner(x) * 2

    x = jnp.ones(17)
    traced = reg.value("fdtpu_jax_trace_seconds_total")
    tracer.clear()
    with tracer.span("item", item=23):
        with tracer.span("dispatch"):
            jax.jit(fdtpu_obs_outer)(x).block_until_ready()
    events = tracer.trace_events()
    got = _spans_of(events, "fdtpu_obs_outer")
    assert set(got) == {"trace", "lower", "compile"}
    assert got["trace"]["args"] == {"parent": "dispatch", "item": 23,
                                    "fun_name": "fdtpu_obs_outer"}
    assert got["lower"]["args"] == {"parent": "dispatch", "item": 23,
                                    "fun_name": "jit(fdtpu_obs_outer)"}
    # in the order jax does them, each inside the dispatch that caused it
    ends = [got[n]["ts"] + got[n]["dur"] for n in ("trace", "lower", "compile")]
    assert ends == sorted(ends)
    # the jitted function traced inside the outer trace is inside its span,
    # not a span or seconds of its own
    assert [e["args"]["fun_name"] for e in events if e["name"] == "trace"] \
        == ["fdtpu_obs_outer"]
    assert reg.value("fdtpu_jax_trace_seconds_total") == pytest.approx(
        traced + got["trace"]["dur"] / 1e6, abs=1e-3)


class _Gate:
    """A value that is ready when the test says so."""

    def __init__(self):
        self.open = threading.Event()

    def block_until_ready(self):
        self.open.wait(10)


def _wait_for(tracer, n):
    deadline = time.monotonic() + 10
    while len(tracer) < n and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(tracer) == n


def test_device_spans_say_how_far_the_loop_ran_ahead():
    from fluxdistributed_tpu.obs import CompletionWatcher

    tracer, gauge = SpanTracer(), []
    w = CompletionWatcher(tracer, on_ahead=gauge.append)
    gates = [_Gate() for _ in range(5)]
    # fed faster than it completes: each item finds one more in flight
    for j, g in enumerate(gates[:4]):
        w.watch(j, g, time.perf_counter())
    for g in gates[:4]:
        g.open.set()
    _wait_for(tracer, 4)
    # and after a wait the loop is in lockstep again
    w.watch(4, gates[4], time.perf_counter())
    gates[4].open.set()
    assert w.close()
    devs = tracer.trace_events()
    assert [e["args"]["item"] for e in devs] == [0, 1, 2, 3, 4]
    assert [e["args"]["ahead"] for e in devs] == [0, 1, 2, 3, 0] == gauge
    assert all(e["args"]["parent"] == "dispatch" for e in devs)


def _tiny_task(classes=4, **kw):
    from fluxdistributed_tpu import mesh as mesh_lib, optim
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.models import SimpleCNN
    from fluxdistributed_tpu.train import prepare_training

    ds = SyntheticDataset(nsamples=64, nclasses=classes, shape=(16, 16, 3))
    return prepare_training(
        SimpleCNN(num_classes=classes), ds, optim.momentum(0.05, 0.9),
        mesh=mesh_lib.data_mesh(8), batch_size=16, cycles=4, **kw)


def _inside(child, parent):
    return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-3)


@pytest.mark.parametrize("how", ["plain", "cache_warmup", "aot"])
def test_prepare_training_leaves_its_phases_as_children_of_prepare(
        how, tmp_path, monkeypatch, request):
    from fluxdistributed_tpu.obs import get_tracer

    kw = {"plain": {}, "aot": {"aot": str(tmp_path / "aot")},
          "cache_warmup": {"warmup": True}}[how]
    if how == "cache_warmup":
        kw["cache_dir"] = request.getfixturevalue("cache_dir")
    ran = {"plain": {"model_init", "step_build"},
           "cache_warmup": {"cache_enable", "model_init", "step_build",
                            "warmup"},
           "aot": {"model_init", "step_build", "aot"}}[how]
    # a head no other test's model has: its init compiles here
    kw["classes"] = {"plain": 41, "cache_warmup": 43, "aot": 47}[how]
    get_tracer().clear()
    _tiny_task(**kw)
    events = get_tracer().trace_events()
    (prepare,) = [e for e in events if e["name"] == "prepare"]
    assert "args" not in prepare  # the outermost: no parent, no item
    children = [e for e in events
                if (e.get("args") or {}).get("parent") == "prepare"]
    # a phase that did not run left no span
    assert {e["name"] for e in children} == ran
    assert all(_inside(e, prepare) for e in children)
    # the children lie apart from each other and cover the call
    tiles = sorted(children, key=lambda e: e["ts"])
    for a, b in zip(tiles, tiles[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
    assert sum(e["dur"] for e in children) >= 0.9 * prepare["dur"]
    # the un-jitted init's programs are compiled inside model_init, and
    # say so
    where = {e["args"].get("parent") for e in events if e["name"] == "compile"}
    assert "model_init" in where and where <= ran
    if how == "aot":
        (aot,) = [e for e in children
                  if e["name"] == "aot" and "source" in e["args"]]
        assert aot["args"]["source"] == "compile"
        step = [e for e in events if e["name"] == "compile"
                and e["args"]["parent"] == "aot"]
        assert step and all(_inside(e, aot) for e in step)
        # the next process finds the file: a load, and no compile in it
        get_tracer().clear()
        _tiny_task(**kw)
        events = get_tracer().trace_events()
        (aot,) = [e for e in events
                  if e["name"] == "aot" and "source" in e["args"]]
        assert aot["args"] == {"parent": "prepare", "source": "load"}
        assert not [e for e in events if e["name"] == "compile"
                    and e["args"].get("parent") == "aot"]
        # its seconds are the span's: the histogram under a counter's
        # name is gone
        assert get_registry().get("fdtpu_aot_seconds_total") is None


def test_train_is_a_span_and_its_items_name_it():
    from fluxdistributed_tpu.obs import get_tracer
    from fluxdistributed_tpu.train import NullLogger, train

    task = _tiny_task()
    task.loader.start = 1  # as a resumed run, or the benchmark's window
    get_tracer().clear()
    train(task, print_every=0, eval_every=0, logger=NullLogger())
    events = get_tracer().trace_events()
    (call,) = [e for e in events if e["name"] == "train"]
    assert call["args"] == {"start_item": 1}
    items = [e for e in events if e["name"] == "item"]
    assert [e["args"]["item"] for e in items] == [1, 2, 3, 4]
    assert all(e["args"]["parent"] == "train" and _inside(e, call)
               for e in items)
    # the watcher is closed inside the call: every completion lies in it
    devs = [e for e in events if e["name"] == "device"]
    assert len(devs) == 3 and all(_inside(e, call) for e in devs)
    assert all(e["args"]["ahead"] >= 0 for e in devs)
    # the gauge an operator scrapes holds the newest item's lead
    assert get_registry().value("fdtpu_train_items_ahead") \
        == devs[-1]["args"]["ahead"]
    assert events[-1] is call or events[-1]["name"] == "train"


# ---------------------------------------------------------------------------
# metrics endpoint (the trainer-side /metrics + /healthz)
# ---------------------------------------------------------------------------

def test_metrics_server_endpoints():
    import urllib.error
    import urllib.request

    r = Registry()
    r.counter("up_total", "").inc(3)
    health = {"ok": True, "steps": 7}
    srv = start_metrics_server(host="127.0.0.1", port=0, registry=r,
                               health_fn=lambda: dict(health))
    base = f"http://127.0.0.1:{srv.port}"
    try:
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            assert "up_total 3" in resp.read().decode()
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as resp:
            assert json.loads(resp.read())["steps"] == 7
        health["ok"] = False  # unhealthy hook -> 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# serve parity: the registry migration preserves every metric name
# ---------------------------------------------------------------------------

class _FakeEngine:
    """Pure-python stand-in for LMEngine: the scheduler's metrics
    surface is exercised without compiling anything."""

    max_slots = 2

    def validate_request(self, prompt_len, max_new_tokens):
        pass

    def prefill(self, slot, prompt, temperature, key):
        return 7, 8  # (first token, padded bucket size)

    def step_decode(self):
        return [1] * self.max_slots

    def reset_slot(self, slot):
        pass

    def compile_stats(self):
        return {"decode_compiles": 1, "prefill_compiles": 2,
                "insert_compiles": 1}


# every series the pre-registry hand-rolled exposition emitted;
# the refactor must keep them all (dashboards and scrapers depend on it)
PRE_REFACTOR_SERIES = [
    "fdtpu_serve_requests_submitted",
    "fdtpu_serve_requests_finished",
    "fdtpu_serve_requests_rejected",
    "fdtpu_serve_prefill_tokens",
    "fdtpu_serve_prefill_padded_tokens",
    "fdtpu_serve_prefill_sec",
    "fdtpu_serve_decode_tokens",
    "fdtpu_serve_decode_sec",
    "fdtpu_serve_ttft_sec_last",
    "fdtpu_serve_ttft_sec_sum",
    "fdtpu_serve_ttft_count",
    "fdtpu_serve_queue_depth",
    "fdtpu_serve_active_slots",
    "fdtpu_serve_max_slots",
    "fdtpu_serve_prefill_tokens_per_sec",
    "fdtpu_serve_decode_tokens_per_sec",
    "fdtpu_serve_ttft_sec_avg",
    "fdtpu_serve_decode_compiles",
    "fdtpu_serve_prefill_compiles",
    "fdtpu_serve_insert_compiles",
]


def _drained_scheduler():
    from fluxdistributed_tpu.serve import Request, Scheduler
    from fluxdistributed_tpu.serve.server import LMServer

    sched = Scheduler(_FakeEngine(), max_queue=4)
    lm = LMServer(sched, vocab=256)
    for prompt in ([1, 2, 3], [4]):
        sched.submit(Request(prompt=prompt, max_new_tokens=2))
    sched.run_until_idle()
    return sched, lm


def test_serve_metrics_text_parity():
    sched, lm = _drained_scheduler()
    text = lm.metrics_text()
    lines = text.splitlines()
    for series in PRE_REFACTOR_SERIES:
        # the exact pre-refactor line shape: `name value`, no labels
        assert any(
            l.startswith(f"{series} ") and not l.startswith("#")
            for l in lines
        ), f"{series} missing from /metrics:\n{text}"
    # values flow through: 2 requests were submitted and finished
    assert "fdtpu_serve_requests_submitted 2" in text
    assert "fdtpu_serve_requests_finished 2" in text
    assert "fdtpu_serve_decode_compiles 1" in text
    # and the registry adds proper TYPE metadata on top
    assert "# TYPE fdtpu_serve_requests_submitted counter" in text
    assert "# TYPE fdtpu_serve_queue_depth gauge" in text


def test_scheduler_metrics_dict_parity():
    sched, _ = _drained_scheduler()
    m = sched.metrics()
    expected = {s[len("fdtpu_serve_"):] for s in PRE_REFACTOR_SERIES}
    assert expected <= set(m), f"missing keys: {expected - set(m)}"
    for k, v in m.items():
        assert isinstance(v, (int, float)), (k, type(v))
    assert m["requests_submitted"] == 2
    assert m["requests_finished"] == 2
    assert m["prefill_tokens"] == 4          # 3 + 1 real prompt tokens
    assert m["prefill_padded_tokens"] == 16  # two bucket-8 prefills
    assert m["decode_tokens"] > 0
    assert m["max_slots"] == 2
    # two schedulers do not share counters (private registry each)
    fresh, _ = _drained_scheduler()
    assert fresh.metrics()["requests_submitted"] == 2


def test_scheduler_close_detaches_shared_registry_callbacks():
    """With a SHARED registry, close() must drop the scrape-time
    closures so a retired engine (and its KV cache) can be collected
    and /metrics stops reporting its stale stats; monotonic counters
    stay (process-cumulative totals are correct across restarts)."""
    from fluxdistributed_tpu.serve import Request, Scheduler
    from fluxdistributed_tpu.serve.server import LMServer

    shared = Registry()
    sched = Scheduler(_FakeEngine(), max_queue=4, registry=shared)
    lm = LMServer(sched, vocab=256)
    sched.submit(Request(prompt=[1], max_new_tokens=1))
    sched.run_until_idle()
    assert "fdtpu_serve_decode_compiles" in lm.metrics_text()
    lm.close()
    text = shared.prometheus_text()
    assert "fdtpu_serve_decode_compiles" not in text
    assert "fdtpu_serve_queue_depth" not in text
    assert "fdtpu_serve_loop_errors" not in text
    assert "fdtpu_serve_requests_finished 1" in text  # counters persist
    # a successor on the same registry re-registers cleanly and
    # continues the cumulative counters
    sched2 = Scheduler(_FakeEngine(), max_queue=4, registry=shared)
    sched2.submit(Request(prompt=[2], max_new_tokens=1))
    sched2.run_until_idle()
    assert sched2.metrics()["requests_finished"] == 2
    assert "fdtpu_serve_queue_depth" in shared.prometheus_text()


# ---------------------------------------------------------------------------
# satellites: ConsoleLogger robustness, trace_analysis path resolution
# ---------------------------------------------------------------------------

def test_console_logger_renders_nested_and_nonscalar(capsys):
    import numpy as np

    from fluxdistributed_tpu.train.logging import ConsoleLogger, NullLogger

    log = ConsoleLogger()
    log.log(
        {
            "loss": 0.123456,
            "phase": {"data_wait": 0.01, "dispatch": np.float32(0.5)},
            "losses": [1.0, 2.0],
            "arr": np.arange(3),
            "note": None,
            "tag": "steady",
        },
        step=7,
    )
    out = capsys.readouterr().out
    assert out.count("\n") == 1  # one record, one line — grep-able
    assert "loss=0.1235" in out
    assert "phase={data_wait:0.0100,dispatch:0.5000}" in out
    assert "losses=[1.0000,2.0000]" in out
    assert "arr=[0 1 2]" in out
    assert "note=None" in out and "tag=steady" in out
    # NullLogger is exported public API
    from fluxdistributed_tpu.train import NullLogger as FromPackage

    assert FromPackage is NullLogger


def test_trace_analysis_resolves_trainer_profile_dir(tmp_path):
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.trace_analysis import resolve_xplane

    # the trainer profile_dir layout: plugins/profile/<session>/<host>.xplane.pb
    old = tmp_path / "plugins" / "profile" / "2026_01_01" / "h.xplane.pb"
    new = tmp_path / "plugins" / "profile" / "2026_02_02" / "h.xplane.pb"
    for i, p in enumerate((old, new)):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"x")
        t = time.time() + i * 100
        import os

        os.utime(p, (t, t))
    assert resolve_xplane(str(tmp_path)) == str(new)  # newest session
    assert resolve_xplane(str(new)) == str(new)       # direct file path
    with pytest.raises(SystemExit, match="xplane"):
        resolve_xplane(str(tmp_path / "plugins" / "profile" / "2026_01_01" / "nope"))
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(SystemExit, match="profile_dir"):
        resolve_xplane(str(empty))
