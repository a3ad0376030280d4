"""The step timeline: with the default ``Observation`` every loader item
leaves its ``item`` / ``data_wait`` / ``dispatch`` / ``device`` /
``assemble`` / ``h2d`` spans under one id in ``obs.get_tracer()``, the
loop is never blocked to obtain them, and the same brackets land in a
profiler session's ``/host:CPU`` plane."""

from __future__ import annotations

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from fluxdistributed_tpu import mesh as mesh_lib, optim
from fluxdistributed_tpu.data import SyntheticDataset
from fluxdistributed_tpu.models import SimpleCNN
from fluxdistributed_tpu.obs import (
    CompletionWatcher,
    Observation,
    Registry,
    SpanTracer,
    get_registry,
    get_tracer,
    jaxmon,
)
from fluxdistributed_tpu.obs.spans import enclosing
from fluxdistributed_tpu.train import NullLogger, prepare_training, train

PER_ITEM = ("item", "data_wait", "dispatch", "device", "assemble", "h2d")


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.data_mesh(8)


def _task(mesh, cycles=6, **kw):
    ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(16, 16, 3))
    return prepare_training(
        SimpleCNN(num_classes=4), ds, optim.momentum(0.05, 0.9),
        mesh=mesh, batch_size=16, cycles=cycles, **kw)


def _by_item(events):
    out: dict = {}
    for e in events:
        item = (e.get("args") or {}).get("item")
        if item is not None:
            out.setdefault(item, {}).setdefault(e["name"], []).append(e)
    return out


def _end(e):
    return e["ts"] + e["dur"]


def test_default_run_leaves_every_items_spans_under_one_id(mesh):
    task = _task(mesh, cycles=6)
    get_tracer().clear()
    hist = get_registry().get("fdtpu_train_phase_seconds")
    device_before = hist.labels(phase="device").count if hist else 0
    train(task, print_every=0, eval_every=4, logger=NullLogger())
    items = _by_item(get_tracer().trace_events())
    loop_tids = set()
    for j in range(6):
        spans = items[j]
        for name in PER_ITEM:
            assert len(spans.get(name, [])) == 1, (j, name)
        item, wait, disp, dev = (spans[n][0] for n in PER_ITEM[:4])
        assert item["args"]["opt_step"] == j
        assert item["args"]["traced"] is False
        # the loop's phases nest in the item's span, on its thread
        for child in (wait, disp):
            assert child["args"]["parent"] == "item"
            assert child["tid"] == item["tid"]
            assert item["ts"] <= child["ts"]
            assert _end(child) <= _end(item) + 1e-3
        assert _end(wait) <= disp["ts"]
        loop_tids.add(item["tid"])
        # the device span is caused by the dispatch and starts no earlier
        assert dev["args"]["parent"] == "dispatch" and "error" not in dev["args"]
        assert dev["ts"] >= _end(disp) - 1e-3
        assert dev["tid"] != item["tid"]
        # worker spans: assemble before the copy, both before the item's
        # dispatch, on a thread that is not the loop's
        asm, h2d = spans["assemble"][0], spans["h2d"][0]
        assert asm["args"]["parent"] == h2d["args"]["parent"] == "item"
        assert _end(asm) <= h2d["ts"] and _end(h2d) <= disp["ts"]
        assert asm["tid"] == h2d["tid"] != item["tid"]
    assert len(loop_tids) == 1
    # completions come in item order, and a device span starts no earlier
    # than the completion before it
    devs = [items[j]["device"][0] for j in range(6)]
    for a, b in zip(devs, devs[1:]):
        assert _end(a) <= _end(b) and b["ts"] >= _end(a) - 1e-3
    # eval ran in items 0 and 4, as their child
    assert [j for j in range(6) if "eval" in items[j]] == [0, 4]
    assert items[4]["eval"][0]["args"]["parent"] == "item"
    # item spans tile the loop's time: each starts where the last ended
    tiles = sorted((e for j in items for e in items[j].get("item", [])),
                   key=lambda e: e["ts"])
    for a, b in zip(tiles, tiles[1:]):
        assert 0 <= b["ts"] - _end(a) < 1e3  # under a millisecond, in us
    # the device series keeps its name and is fed by the watcher
    assert get_registry().get("fdtpu_train_phase_seconds").labels(
        phase="device").count == device_before + 6


def test_chunked_items_share_the_loader_items_id(mesh):
    task = _task(mesh, cycles=8, steps_per_call=2)
    get_tracer().clear()
    train(task, print_every=0, eval_every=0, logger=NullLogger())
    items = _by_item(get_tracer().trace_events())
    for j in range(4):
        assert {n for n in PER_ITEM} <= set(items[j])
        assert items[j]["item"][0]["args"]["opt_step"] == 2 * j


def test_profiler_session_holds_the_annotations_with_their_item(mesh, tmp_path):
    from jax.profiler import ProfileData

    task = _task(mesh, cycles=4)
    get_tracer().clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        train(task, print_every=0, eval_every=0, logger=NullLogger())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    seen: dict = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("fdtpu/"):
                seen.setdefault(e.name, []).append(dict(e.stats))
    assert sorted(s["item"] for s in seen["fdtpu/dispatch"]) == [0, 1, 2, 3]
    assert sorted(s["step_num"] for s in seen["fdtpu/item"])[:4] == [0, 1, 2, 3]
    assert {"fdtpu/data_wait", "fdtpu/assemble", "fdtpu/h2d"} <= set(seen)
    assert {s["item"] for s in seen["fdtpu/h2d"]} == {0, 1, 2, 3}
    # and the ring says which items a session recorded
    items = _by_item(get_tracer().trace_events())
    assert all(items[j]["item"][0]["args"]["traced"] is True for j in range(4))


class _Boom:
    def block_until_ready(self):
        raise RuntimeError("device fell over")


def test_error_at_completion_is_recorded_and_the_loop_goes_on(mesh):
    task = _task(mesh, cycles=5)
    step_fn, calls = task.step_fn, []

    def step(state, batch):
        new_state, metrics = step_fn(state, batch)
        calls.append(1)
        if len(calls) == 3:
            metrics = dict(metrics, late=_Boom())
        return new_state, metrics

    task.step_fn = step
    get_tracer().clear()
    steps_before = get_registry().value("fdtpu_train_steps_total")
    train(task, print_every=0, eval_every=0, logger=NullLogger())
    assert get_registry().value("fdtpu_train_steps_total") == steps_before + 5
    items = _by_item(get_tracer().trace_events())
    assert "device fell over" in items[2]["device"][0]["args"]["error"]
    assert all("error" not in items[j]["device"][0]["args"] for j in (0, 1, 3, 4))


def test_watcher_never_blocks_the_caller_and_close_has_a_time_limit():
    release = threading.Event()

    class Slow:
        def block_until_ready(self):
            release.wait(10)

    tracer, done = SpanTracer(), []
    w = CompletionWatcher(tracer, done.append)
    t0 = time.perf_counter()
    w.watch(0, Slow(), t0)
    w.watch(1, jnp.ones(3), time.perf_counter())
    assert time.perf_counter() - t0 < 1.0 and len(tracer) == 0
    assert w.close(timeout=0.05) is False  # still waiting: says so, returns
    release.set()
    deadline = time.monotonic() + 10
    while len(tracer) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    first, second = tracer.trace_events()
    assert (first["name"], first["args"]["item"]) == ("device", 0)
    assert second["args"]["item"] == 1
    # item 1 was ready at once, but the device held it only after item 0
    assert second["ts"] >= first["ts"] + first["dur"] - 1e-3
    assert len(done) == 2 and done[0] > done[1] >= 0


def test_ring_stays_bounded_over_a_long_run():
    tracer = SpanTracer(max_events=64)
    for j in range(500):
        with tracer.span("item", item=j):
            with tracer.span("dispatch"):
                pass
        tracer.record("device", 0.0, 1.0, item=j)
    assert len(tracer) == 64 and tracer.dropped == 1500 - 64
    assert tracer.trace_events()[-1]["args"]["item"] == 499
    # the process tracer holds at least the last 1,000 loader items: six
    # spans an item, and eval, checkpoint and compile now and then
    assert get_tracer()._events.maxlen >= 8 * 1000


def test_compile_span_carries_the_item_during_which_it_fell():
    jaxmon.install()
    tracer = get_tracer()
    tracer.clear()
    f = jax.jit(lambda x: x * 3 + 41)
    with tracer.span("item", item=17):
        assert enclosing() == {"parent": "item", "item": 17}
        with tracer.span("dispatch"):
            f(jnp.ones(5)).block_until_ready()
        # a compile on a thread with no span of its own falls during the
        # newest open item
        t = threading.Thread(target=lambda: jax.jit(lambda x: x - 43)(
            jnp.ones(6)).block_until_ready())
        t.start()
        t.join(60)
    assert enclosing() == {}
    compiles = [e for e in tracer.trace_events() if e["name"] == "compile"]
    assert len(compiles) >= 2
    assert all(e["args"]["item"] == 17 for e in compiles)
    # each names the span during which it fell: the loop's own phase, or
    # the item where the compiling thread had no span open
    assert [e["args"]["parent"] for e in compiles
            if "<lambda>" in e["args"]["fun_name"]] == ["dispatch", "item"]
    disp = next(e for e in tracer.trace_events() if e["name"] == "dispatch")
    first = compiles[0]
    assert disp["ts"] <= first["ts"] + first["dur"] <= disp["ts"] + disp["dur"] + 1e3
    f(jnp.ones(5))  # compiled already: no span
    assert len([e for e in tracer.trace_events()
                if e["name"] == "compile"]) == len(compiles)


def test_profile_dir_starts_the_profiler_with_the_python_tracer_off(
        mesh, tmp_path, monkeypatch):
    started, stopped = [], []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: started.append((d, profiler_options)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: stopped.append(1))
    train(_task(mesh, cycles=4), print_every=0, eval_every=0,
          logger=NullLogger(), profile_dir=str(tmp_path), profile_start=1,
          profile_steps=2)
    ((d, opts),) = started
    assert d == str(tmp_path) and stopped == [1]
    assert opts.python_tracer_level == 0 and opts.host_tracer_level == 1


def test_observation_has_one_loop_whatever_is_exported(mesh, tmp_path):
    """``Observation.full`` and ``trace_path`` only export the ring: the
    options that made a second, serialised loop are gone."""
    import dataclasses
    import json

    fields = {f.name for f in dataclasses.fields(Observation)}
    assert not {"device_" + "sync", "tracer"} & fields
    path = tmp_path / "t.json"
    obs = Observation(registry=Registry(), trace_path=str(path))
    get_tracer().clear()
    train(_task(mesh, cycles=3), print_every=0, eval_every=0,
          logger=NullLogger(), observation=obs)
    doc = json.loads(path.read_text())
    assert doc["otherData"]["origin_unix_time"] > 0
    names = [e["name"] for e in doc["traceEvents"]]
    assert all(names.count(n) >= 3 for n in PER_ITEM)
    assert obs.registry.get("fdtpu_train_phase_seconds").labels(
        phase="device").count == 3


def test_train_step_names_grad_and_update_in_the_lowered_text(mesh):
    from fluxdistributed_tpu.parallel.dp import TrainState, make_train_step

    def loss_fn(p, mstate, batch, train):
        y = batch["x"] @ p["w"]
        return jnp.mean((y - batch["y"]) ** 2), (mstate, y)

    opt = optim.momentum(0.1, 0.9)
    params = {"w": jnp.ones((4, 2))}
    state = TrainState(params=params, opt_state=opt.init(params),
                       model_state={}, step=jnp.zeros((), jnp.int32))
    batch = {"x": jnp.ones((16, 4)), "y": jnp.zeros((16, 2))}
    for k in (1, 2):
        step = make_train_step(loss_fn, opt, mesh, donate=False,
                               steps_per_call=k)
        b = batch if k == 1 else jax.tree.map(
            lambda x: jnp.stack([x] * k), batch)
        lowered = step.lower(state, b)
        text = lowered.as_text(debug_info=True)
        assert "fdtpu/grad" in text and "fdtpu/update" in text
        # metadata only: the jitted function keeps its name
        assert ("jit_step" if k == 1 else "jit_chunked") in text
