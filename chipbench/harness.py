"""The benchmark's harness: one cell, one seed, one window.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (a file of sizes with its plain reference beside it) under
a traffic mix (a file of parameters).  Everything that belongs to one
configuration, one mix or one metric sits in a file of its own that is
found by name, so a later change adds cells, configurations and metrics
as new files and new entries and edits nothing that is here.

The window drives the users' entry, ``prepare_training`` then ``train``
with ``bin/driver.py``'s defaults, and ends at a step boundary through
the loop's own stop (``handle_signals=True``, no checkpoint directory:
the harness sends itself SIGTERM when the time is up and catches
``Preempted``).  The rate is every optimizer step completed, over the
wall time from the call of ``train`` to the barrier on the final state.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = "BENCHMARK.json"
BIG = 10 ** 9  # loader items: the window ends by the clock, never by the data


class BenchError(RuntimeError):
    """The run cannot give a result: no chip, a name that finds no file."""


# -- finding files by name ---------------------------------------------------

def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, MANIFEST)) as f:
        return json.load(f)


def find_file(root: str, manifest: dict, *relative: str) -> str:
    for base in manifest["paths"]:
        path = os.path.join(root, base, *relative)
        if os.path.isfile(path):
            return path
    raise BenchError(f"no {os.path.join(*relative)} under any of "
                     f"{manifest['paths']}")


def load_module(path: str):
    name = "chipbench_file_" + os.path.splitext(
        os.path.relpath(path, ROOT))[0].replace(os.sep, "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict      # the configuration's file, as run
    ref: object       # its plain reference (the .py beside the file)
    traffic: dict     # the mix's parameters
    limits: dict      # a limit for each number compared
    metrics: dict     # {"end_to_end": [...], "per_layer": [...]} entries


def load_cell(name: str, root: str = ROOT, manifest: dict | None = None) -> Cell:
    manifest = manifest or load_manifest(root)
    work = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if work is None:
        raise BenchError(f"no workload {name!r} in {MANIFEST}")
    entry = next(c for c in manifest["configs"] if c["name"] == work["config"])
    cfg_path = os.path.join(root, entry["file"])
    with open(cfg_path) as f:
        config = json.load(f)
    with open(find_file(root, manifest, "traffic",
                        work["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(find_file(root, manifest, "limits", name + ".json")) as f:
        limits = json.load(f)["limits"]

    def mine(m, e2e):
        cells = m.get("workloads")
        if cells is not None:
            return name in cells
        if e2e:
            return True
        # a per-layer metric without a list is due wherever the metric
        # it moves is reported
        moved = next(x for x in manifest["end_to_end"] if x["name"] == m["moves"])
        return mine(moved, True)

    return Cell(
        name=name, chips=int(work["chips"]), config=config,
        ref=load_module(os.path.splitext(cfg_path)[0] + ".py"),
        traffic=traffic, limits=limits,
        metrics={"end_to_end": [m for m in manifest["end_to_end"] if mine(m, True)],
                 "per_layer": [m for m in manifest["per_layer"] if mine(m, False)]})


def load_peaks(kind: str, root: str = ROOT, manifest: dict | None = None) -> dict:
    manifest = manifest or load_manifest(root)
    with open(find_file(root, manifest, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind == "source":
        raise BenchError(f"no peaks recorded for device_kind {kind!r}: add it "
                         "to peaks.json with its source")
    return table[kind]


def read_metric(root, manifest, name: str, ctx: dict):
    reader = load_module(find_file(root, manifest, "metrics", name + ".py"))
    return reader.read(ctx)


# -- the device ---------------------------------------------------------------

def require_chips(chips: int) -> list:
    """The devices the cell runs on; no accelerator, or fewer chips than
    the cell asks for, is an error and never a CPU number."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"the benchmark measures a TPU; jax found "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, jax found {len(devs)}")
    return devs[:chips]


class MemorySampler(threading.Thread):
    """Peak bytes on the fullest chip, from set-up to the window's end.

    On the TPU the allocator's ``bytes_in_use`` counts live arrays only
    and the compiled programs' temporaries sit in ``bytes_reserved``
    (PERF.md, "Memory"), so a chip holds their sum.  The two peaks the
    allocator keeps do not fall together (arrays peak while the model is
    initialised, before any program is loaded), so their sum can pass
    the chip's size: the sum is sampled instead, ten times a second."""

    def __init__(self, devices, every_s: float = 0.1):
        super().__init__(daemon=True)
        self.devices, self.every_s = devices, every_s
        self.peak = None
        self.done = threading.Event()

    def sample(self):
        for d in self.devices:
            s = d.memory_stats() or {}
            if "bytes_in_use" in s:
                now = s["bytes_in_use"] + s.get("bytes_reserved", 0)
                self.peak = now if self.peak is None else max(self.peak, now)

    def run(self):
        while not self.done.wait(self.every_s):
            self.sample()

    def finish(self) -> int | None:
        self.done.set()
        self.join()
        self.sample()
        return None if self.peak is None else int(self.peak)


def seed_key(seed: int):
    """A key from any whole number, also one past 32 signed bits."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


# -- set-up -------------------------------------------------------------------

class Recorder:
    """Stands around the compiled step for the first steps of a run and
    keeps what the comparison needs: each step's loss and batch, and the
    first gradient's norms as the optimizer got it.  After the last of
    them it asks the loop to stop at its next step boundary."""

    def __init__(self, step_fn, steps: int, first_grad_norms):
        self.step_fn, self.steps = step_fn, steps
        self.first_grad_norms = first_grad_norms
        self.losses, self.batches, self.grad1 = [], [], None

    def __call__(self, state, batch):
        new_state, metrics = self.step_fn(state, batch)
        k = len(self.losses)
        if k < self.steps:
            self.losses.append(metrics["loss"])
            self.batches.append(batch)
            if k == 0:
                self.grad1 = self.first_grad_norms(new_state.opt_state)
            if k == self.steps - 1:
                signal.raise_signal(signal.SIGTERM)
        return new_state, metrics


def run_train(task, **kw) -> int:
    """``train`` until the loop's own stop; the next loader item."""
    from fluxdistributed_tpu.faults import Preempted
    from fluxdistributed_tpu.train import train

    try:
        train(task, print_every=0, eval_every=0, handle_signals=True, **kw)
    except Preempted as e:
        return int(e.next_item)
    raise BenchError("train() came back before the loop was asked to stop")


def set_up(cell: Cell, seed: int, devices, cache_dir: str | None):
    """Build the task, put the seeded weights in, and drive it through
    its first steps by the window's own call and feed.  Returns the task
    (handed on to the window as it is), the pool and what the program's
    first steps produced."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    import fluxdistributed_tpu as fd
    from fluxdistributed_tpu.train import prepare_training

    from . import reference, refcommon
    from .pool import PoolDataset

    cfg, mix = cell.config, cell.traffic
    pool = PoolDataset(seed, mix["pool_rows"], cfg["image"], cfg["num_classes"])
    model = getattr(fd.models, cfg["model"]["factory"])(**cfg["model"]["kwargs"])
    opt_name, hp = cfg["optimizer"]["factory"], cfg["optimizer"]["kwargs"]
    optimizer = getattr(fd.optim, opt_name)(**hp)
    mesh = fd.data_mesh(devs=devices)
    task = prepare_training(
        model, pool, optimizer, mesh=mesh, batch_size=mix["global_batch"],
        cycles=BIG, buffersize=mix["buffersize"], seed=seed & 0x7FFFFFFF,
        steps_per_call=mix["steps_per_call"], cache_dir=cache_dir,
        **cfg["prepare"])

    # the weights are the benchmark's own, from the seed, so that the
    # reference can make the same ones without taking any from the program
    repl = NamedSharding(mesh, PartitionSpec())
    make = jax.jit(lambda k: cell.ref.make_params(cfg, k), out_shardings=repl)
    params, mstate = make(seed_key(seed))
    want = jax.tree.map(lambda x: (x.shape, x.dtype), (task.state.params,
                                                        task.state.model_state))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), (params, mstate))
    if want != got:
        raise BenchError("the configuration's seeded weights do not have the "
                         "shape of the program's parameter tree")
    task.state = task.state.replace(params=params, model_state=mstate)
    del params, mstate

    first_grad = refcommon.OPTIMIZERS[opt_name][2]
    step_fn = task.step_fn
    rec = Recorder(step_fn, mix["check_steps"],
                   lambda s: reference.leaf_norms(first_grad(hp, s)))
    task.step_fn = rec
    task.loader.start = run_train(task)
    task.step_fn = step_fn
    if len(rec.losses) != mix["check_steps"]:
        raise BenchError(f"the loop ran {len(rec.losses)} steps where "
                         f"{mix['check_steps']} were asked for")

    p0, s0 = make(seed_key(seed))
    names = reference.leaf_names(task.state.params)
    first = {
        "losses": [float(x) for x in rec.losses],
        "grad1": dict(zip(names, map(float, rec.grad1))),
        "delta": dict(zip(names, map(float, reference.delta_norms(
            task.state.params, p0)))),
        "state_delta": dict(zip(
            reference.leaf_names(task.state.model_state),
            map(float, reference.delta_norms(task.state.model_state, s0)))),
        "steps": int(task.state.step),
    }
    del p0, s0
    # the rows each step was fed, read back from the device
    fed = [(np.asarray(b["image"]), np.asarray(b["label"])) for b in rec.batches]
    del rec
    return task, pool, first, fed


def fed_rows(pool, fed):
    """Turn what the steps were fed into the reference's input: the
    pool's own rows and integer labels.  Counts every fed row that is not
    a pool row, is fed twice in a batch, or carries another label."""
    import numpy as np

    batches, wrong = [], 0
    for images, onehot in fed:
        rows = pool.rows_of(images)
        ok = rows >= 0
        safe = np.where(ok, rows, 0)
        ok &= (images == pool.images[safe]).reshape(len(images), -1).all(axis=1)
        ok &= onehot.argmax(axis=-1) == pool.labels[safe]
        ok &= onehot.sum(axis=-1) == 1
        wrong += int((~ok).sum()) + (len(rows) - len(set(rows.tolist())))
        batches.append((pool.images[safe], pool.labels[safe]))
    return batches, wrong


# -- the window ---------------------------------------------------------------

def registry_sums() -> dict:
    """The host-clock sums and counts the per-layer readers take."""
    from fluxdistributed_tpu.obs import get_registry

    reg = get_registry()
    out = {}
    phase = reg.get("fdtpu_train_phase_seconds")
    for p in ("data_wait", "dispatch"):
        out[f"phase_{p}_s"] = phase.cell_sum(p) if phase else 0.0
        out[f"phase_{p}_n"] = phase.cell_count(p) if phase else 0
    for short, name in (("h2d", "fdtpu_data_h2d_seconds"),
                        ("assemble", "fdtpu_data_assemble_seconds")):
        h = reg.get(name)
        out[f"{short}_s"] = h.cell_sum() if h else 0.0
        out[f"{short}_n"] = h.cell_count() if h else 0
    out["steps_total"] = reg.value("fdtpu_train_steps_total")
    return out


class TraceSlice(threading.Thread):
    """A profiler trace over a slice of the window, started and stopped
    from this thread so that the loop stays as it is when not traced (the
    loop's own ``profile_dir`` hook blocks on the state at the slice's
    end).  The python tracer is off: with it on, a traced ResNet-50 loop
    ran at half its rate (PERF.md, Findings)."""

    def __init__(self, directory: str, after_s: float, for_s: float):
        super().__init__(daemon=True)
        self.directory, self.after_s, self.for_s = directory, after_s, for_s
        self.closed = threading.Event()
        self.error = None

    def run(self):
        import jax

        if self.closed.wait(self.after_s):
            return
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.directory, profiler_options=opts)
            self.closed.wait(self.for_s)
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - handed to the caller below
            self.error = e

    def finish(self):
        self.closed.set()
        self.join()
        if self.error is not None:
            raise BenchError(f"the profiler failed: {self.error!r}")


def window(task, seconds: float, trace_dir: str | None, mix: dict) -> dict:
    import jax

    from fluxdistributed_tpu import compilation

    step0 = int(task.state.step)
    before, compiled0 = registry_sums(), compilation.compile_metrics()
    timer = threading.Timer(seconds, os.kill, (os.getpid(), signal.SIGTERM))
    timer.daemon = True
    tracer = (TraceSlice(trace_dir, mix["trace_after_s"], mix["trace_for_s"])
              if trace_dir else None)
    t0 = time.perf_counter()
    timer.start()
    if tracer:
        tracer.start()
    try:
        run_train(task)
    finally:
        timer.cancel()
    jax.block_until_ready(task.state)
    wall = time.perf_counter() - t0
    if tracer:
        tracer.finish()
    after, compiled1 = registry_sums(), compilation.compile_metrics()
    return {"seconds": wall, "steps": int(task.state.step) - step0,
            "registry": {k: after[k] - before[k] for k in after},
            "compile_at_start": compiled0, "compile_at_end": compiled1}


# -- one run ------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: str = ROOT, manifest: dict | None = None,
             devices=None, peaks: dict | None = None,
             cache_dir: str | None = None) -> dict:
    """One run: set-up, window, comparison.  The result line as a dict.
    ``devices`` and ``peaks`` are for the tests, which run the same path
    on the CPU at a size they pass in; the command never passes them."""
    import jax

    from . import reference, trace as trace_lib

    manifest = manifest or load_manifest(root)
    if devices is None:
        devices = require_chips(cell.chips)
    if peaks is None:
        peaks = load_peaks(devices[0].device_kind, root, manifest)
    sampler = MemorySampler(devices)
    sampler.start()
    task, pool, first, fed = set_up(cell, seed, devices, cache_dir)
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        setup_s = time.perf_counter() - t_process
        win = window(task, seconds, trace_dir, cell.traffic)
        mem = sampler.finish()
        reduced, lines = None, None
        if trace_dir:
            loaded = trace_lib.load(trace_dir)
            reduced, lines = trace_lib.reduce(loaded), loaded["lines"]
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    skipped = int(task.num_missed)
    # free the program's state before the reference takes the chip
    del task
    gc.collect()

    batches, wrong_rows = fed_rows(pool, fed)
    del fed
    t_ref = time.perf_counter()
    with jax.default_device(devices[0]):
        ref = reference.first_steps(cell.config, cell.ref, seed_key(seed), batches)
    reference_s = time.perf_counter() - t_ref
    numbers = reference.compare(first, ref, cell.ref)
    numbers["feed_mismatch"] = float(wrong_rows)
    numbers["steps_off"] = float(abs(first["steps"] - cell.traffic["check_steps"]))
    limits = dict(cell.limits, feed_mismatch=0.0, steps_off=0.0)
    correct, table = reference.judge(numbers, limits)

    images = win["steps"] * cell.traffic["global_batch"]
    ctx = {
        "cell": cell.name, "chips": cell.chips, "config": cell.config,
        "traffic": cell.traffic, "peaks": peaks, "setup_s": setup_s,
        "window": {"seconds": win["seconds"], "steps": win["steps"],
                   "images": images, **{k: win[k] for k in
                                        ("registry", "compile_at_start",
                                         "compile_at_end")}},
        "flops_per_image": 6.0 * cell.ref.forward_macs(cell.config),
        "memory_peak_bytes": mem, "trace": reduced,
    }
    metrics = {}
    for m in cell.metrics["per_layer" if trace else "end_to_end"]:
        v = read_metric(root, manifest, m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": win["steps"] + skipped,
           "failed": skipped, "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["notes"] = {"window_s": win["seconds"], "steps": win["steps"],
                    "reference_s": reference_s,
                    "run_s": time.perf_counter() - t_process,
                    "losses": first["losses"], "ref_losses": ref["losses"],
                    "not_compared": {k: v for k, v in numbers.items()
                                     if k not in limits},
                    "worst_leaf": reference.worst_leaves(first, ref)}
    if trace and not reduced:
        out["notes"]["trace_lines"] = lines  # where the reducer found nothing
    out["compared"] = table
    return out
