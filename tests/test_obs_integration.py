"""Observability wired end-to-end (the ISSUE 3 acceptance surface).

Fast tier: a CPU training smoke run with ``Observation.full`` must land
the step counter, per-phase histograms, compile count and OOM-skip
counter in the process registry, export a valid Chrome trace-event
file, and expose it all over the stdlib ``/metrics`` endpoint.

Slow tier: ``bin/driver.py`` with the obs flags end-to-end, and the
trainer ``profile_dir`` → ``benchmarks/trace_analysis.py`` handoff
(captures a real profiler trace — too heavy for the fast loop).
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

from fluxdistributed_tpu import mesh as mesh_lib, optim
from fluxdistributed_tpu.data import SyntheticDataset
from fluxdistributed_tpu.models import SimpleCNN
from fluxdistributed_tpu.obs import Observation, get_registry
from fluxdistributed_tpu.train import NullLogger, prepare_training, train

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.data_mesh(8)


def _task(mesh, cycles=6):
    ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(16, 16, 3))
    return prepare_training(
        SimpleCNN(num_classes=4), ds, optim.momentum(0.05, 0.9),
        mesh=mesh, batch_size=16, cycles=cycles,
    )


def test_train_smoke_populates_registry_and_trace(mesh, tmp_path):
    reg = get_registry()
    trace_path = str(tmp_path / "run.trace.json")
    profile_path = str(tmp_path / "run.profile.json")
    obs = Observation.full(trace_path=trace_path,
                           jsonl_path=str(tmp_path / "run.jsonl"),
                           profile_path=profile_path)
    steps_before = reg.value("fdtpu_train_steps_total")
    stalls_before = reg.value("fdtpu_watchdog_stalls_total")

    train(_task(mesh), print_every=2, eval_every=3, logger=NullLogger(),
          observation=obs)

    # step counter + per-phase histograms + compile count + OOM skips —
    # the acceptance criterion's /metrics payload
    assert reg.value("fdtpu_train_steps_total") == steps_before + 6
    hist = reg.get("fdtpu_train_phase_seconds")
    for phase in ("data_wait", "dispatch", "device", "eval"):
        assert hist.labels(phase=phase).count > 0, phase
    assert reg.value("fdtpu_jax_compiles_total") > 0
    assert reg.value("fdtpu_train_oom_skipped_total") >= 0
    # the loader reported its side of the pipeline
    assert reg.value("fdtpu_data_batches_total") > 0
    assert reg.get("fdtpu_data_h2d_seconds").cell_count() > 0
    # a steady 6-cycle run must not trip the watchdog
    assert reg.value("fdtpu_watchdog_stalls_total") == stalls_before

    # the span file is valid Chrome trace-event JSON with the step phases
    doc = json.loads(pathlib.Path(trace_path).read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"data_wait", "dispatch", "device", "h2d"} <= names
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and "ts" in e and "dur" in e

    # the jsonl sink appended print-cadence + final snapshots
    lines = [json.loads(l)
             for l in (tmp_path / "run.jsonl").read_text().splitlines()]
    assert lines and lines[-1]["final"]
    assert lines[-1]["metrics"]["fdtpu_train_steps_total"] >= 6

    # the cost-profile artifact: versioned, topology-verified, with the
    # REAL step's static price and this run's measured phases inside
    from fluxdistributed_tpu.obs import Profile

    prof = Profile.load(profile_path).verify(mesh)
    assert prof.static["step"]["flops"] > 0
    assert prof.measured["phases"]["dispatch"]["count"] >= 6
    assert prof.meta["model"] == "SimpleCNN" and prof.meta["steps"] == 6
    # the v2 sections: exact state bytes, the step's memory_analysis
    # breakdown, and the compiled step's collective ledger (the GSPMD
    # dp step all-reduces its gradients over the 8-device data axis)
    assert prof.schema == "fdtpu-profile/v2"
    assert prof.memory["state"]["param_bytes"] > 0
    assert prof.memory["step"] is None or (
        prof.memory["step"]["peak_bytes"] > 0)
    hlo = {e["kind"] for e in prof.comms["step"].get("hlo", [])}
    assert "all_reduce" in hlo


def test_train_metrics_scrapeable_over_http(mesh):
    import urllib.request

    from fluxdistributed_tpu.obs import start_metrics_server

    train(_task(mesh, cycles=2), print_every=0, eval_every=0,
          logger=NullLogger())  # the default Observation
    srv = start_metrics_server(host="127.0.0.1", port=0)
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            text = r.read().decode()
        for series in ("fdtpu_train_steps_total",
                       "fdtpu_train_phase_seconds_bucket",
                       "fdtpu_jax_compiles_total",
                       "fdtpu_train_oom_skipped_total",
                       "fdtpu_data_prefetch_depth"):
            assert series in text, f"{series} missing:\n{text[:2000]}"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=10) as r:
            assert json.loads(r.read())["ok"]
    finally:
        srv.stop()


def _driver_env():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.slow
def test_driver_obs_flags_end_to_end(tmp_path):
    """--trace-events/--metrics-jsonl/--steady-after plus the cold-start
    flags (--compile-cache/--aot/--prewarm) and --strict-checks through
    the CLI: artifacts appear and the run completes (a real training
    run passes the armed NaN-debug + transfer-guard first steps)."""
    trace = tmp_path / "driver.trace.json"
    jsonl = tmp_path / "driver.jsonl"
    cache = tmp_path / "compile-cache"
    aot = tmp_path / "aot"
    out = subprocess.run(
        [sys.executable, os.path.join("bin", "driver.py"),
         "--model", "SimpleCNN", "--dataset", "synthetic",
         "--num-classes", "4", "--image-size", "16",
         "--batch-size", "16", "--cycles", "4",
         "--print-every", "1", "--eval-every", "0",
         "--trace-events", str(trace), "--metrics-jsonl", str(jsonl),
         "--steady-after", "3",
         "--compile-cache", str(cache), "--aot", str(aot), "--prewarm",
         "--strict-checks",
         "--platform", "cpu", "--local-devices", "8"],
        capture_output=True, text=True, timeout=600, env=_driver_env(),
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "done: 4 steps" in out.stdout, out.stdout[-2000:]
    doc = json.loads(trace.read_text())
    assert {"data_wait", "dispatch", "device"} <= {
        e["name"] for e in doc["traceEvents"]}
    lines = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert lines[-1]["metrics"]["fdtpu_train_steps_total"] == 4
    # cold-start artifacts: a topology-namespaced populated cache dir
    # and one serialized train-step executable
    (ns,) = os.listdir(cache)
    assert os.listdir(cache / ns), "compile cache stayed empty"
    assert any(f.startswith("train_step-") for f in os.listdir(aot))
    # --prewarm declared its cost before step 0
    assert "warmup:" in out.stdout, out.stdout[-2000:]


@pytest.mark.slow
def test_driver_metrics_port_scrape_mid_run(tmp_path):
    """--metrics-port serves /metrics + /healthz DURING training: poll
    until the endpoint answers, scrape, then let the run finish."""
    import socket
    import time
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, os.path.join("bin", "driver.py"),
         "--model", "SimpleCNN", "--dataset", "synthetic",
         "--num-classes", "4", "--image-size", "16",
         "--batch-size", "16", "--cycles", "300",
         "--print-every", "0", "--eval-every", "0",
         "--metrics-port", str(port),
         "--platform", "cpu", "--local-devices", "8"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_driver_env(), cwd=str(REPO),
    )
    text = None
    try:
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                break  # finished before we scraped — fail below with logs
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=2) as r:
                    text = r.read().decode()
                break
            except OSError:
                time.sleep(0.2)
        assert text is not None, (
            f"never scraped /metrics; rc={proc.poll()}\n"
            f"{proc.stderr.read()[-3000:] if proc.poll() is not None else ''}"
        )
        for series in ("fdtpu_train_phase_seconds_bucket",
                       "fdtpu_jax_compiles_total",
                       "fdtpu_train_oom_skipped_total"):
            assert series in text, f"{series} missing:\n{text[:2000]}"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
            assert json.loads(r.read())["ok"]
    finally:
        proc.terminate()
        proc.wait(timeout=30)


@pytest.mark.slow
def test_profile_dir_to_trace_analysis_handoff(mesh, tmp_path, capsys):
    """A trainer profile_dir capture goes straight through the bench
    analyzer (one analyzer for production and bench traces)."""
    sys.path.insert(0, str(REPO))
    from benchmarks.trace_analysis import analyze

    pdir = str(tmp_path / "prof")
    train(_task(mesh, cycles=4), print_every=0, eval_every=0,
          logger=NullLogger(), profile_dir=pdir, profile_start=1,
          profile_steps=2)
    analyze(pdir, top=5)
    out = capsys.readouterr().out
    assert "by op class:" in out
    assert "top 5 ops by total time:" in out
