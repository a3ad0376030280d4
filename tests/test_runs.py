"""Run-history ledger + regression gating (obs/runs.py, bin/trends.py).

The detector's contract, pinned: baselines are per-(metric, topology)
rolling medians over error-free predecessors; exactly AT tolerance
passes; movement past tolerance in the GOOD direction is a note, not a
failure (memory-baseline semantics); a topology with <2 observations
has nothing to gate against.  Plus the ``--ingest`` backfill (field
preservation + idempotency), ``--check`` exit codes, and the
postmortem merge."""

import importlib.util
import json
import math
import os
import shutil

from fluxdistributed_tpu.obs import Registry
from fluxdistributed_tpu.obs import runs as runs_lib
from fluxdistributed_tpu.obs.flight import FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench(throughput, fp="TPUv4:dp8", error=None, **metrics):
    metrics["throughput"] = throughput
    return runs_lib.run_record("bench", fingerprint=fp, phase="done",
                               error=error, metrics=metrics)


# ---------------------------------------------------------------------------
# record normalization + ledger IO
# ---------------------------------------------------------------------------


def test_run_record_drops_poisonous_metrics():
    """NaN/inf/non-numeric values must never reach a median."""
    rec = runs_lib.run_record(
        "bench", fingerprint="fp",
        metrics={"throughput": 100.0, "bad_nan": math.nan,
                 "bad_inf": math.inf, "bad_str": "fast", "ok_int": 3},
        error="x" * 1000)
    assert rec["schema"] == runs_lib.RUNS_SCHEMA
    assert rec["metrics"] == {"throughput": 100.0, "ok_int": 3.0}
    assert len(rec["error"]) == 500  # truncated, never unbounded


def test_append_load_roundtrip_tolerates_torn_tail(tmp_path):
    p = str(tmp_path / "runs.jsonl")
    assert runs_lib.append_run(p, _bench(100.0))
    assert runs_lib.append_run(p, _bench(101.0))
    with open(p, "a") as f:
        f.write('{"schema": "fdtpu-runs/v1", "kind": "ben')  # the tear
    runs = runs_lib.load_runs(p)
    assert [r["metrics"]["throughput"] for r in runs] == [100.0, 101.0]
    assert runs_lib.load_runs(str(tmp_path / "absent.jsonl")) == []


def test_append_run_never_raises(tmp_path, capsys):
    # a regular file poses as the parent dir: fails even as root
    (tmp_path / "ro").write_text("not a directory")
    assert runs_lib.append_run(str(tmp_path / "ro" / "runs.jsonl"),
                               _bench(1.0)) is False
    assert "obs.runs" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the regression detector
# ---------------------------------------------------------------------------


def test_exactly_at_tolerance_passes_strictly_beyond_fails():
    """The 10% throughput tolerance is inclusive: 90 vs baseline 100
    passes, 89 fails."""
    history = [_bench(100.0), _bench(100.0), _bench(100.0)]
    at_edge = runs_lib.check_regressions(history + [_bench(90.0)])
    assert at_edge["failures"] == []
    assert any(r["verdict"] == "ok" and r["metric"] == "throughput"
               for r in at_edge["rows"])
    beyond = runs_lib.check_regressions(history + [_bench(89.0)])
    assert len(beyond["failures"]) == 1
    assert "throughput" in beyond["failures"][0]
    assert "bad direction" in beyond["failures"][0]


def test_unknown_topology_and_first_run_are_notes_not_failures():
    """One observation — or a fingerprint nobody has seen — has no
    baseline; CI must not gate on it."""
    out = runs_lib.check_regressions([_bench(50.0, fp="TPUv5:new")])
    assert out["failures"] == []
    assert any("no baseline yet" in n for n in out["notes"])
    assert out["rows"][0]["verdict"] == "no-baseline"
    # fingerprint=None groups under "unknown" and behaves the same
    out = runs_lib.check_regressions(
        [runs_lib.run_record("bench", metrics={"throughput": 5.0})])
    assert out["failures"] == []


def test_shrinking_lower_is_better_metric_is_a_note():
    """Memory-baseline semantics: peak HBM (or compile time) dropping
    past tolerance means 're-record the baseline', never 'fail CI'."""
    mk = lambda v: runs_lib.run_record(
        "bench", fingerprint="fp", metrics={"peak_hbm_bytes": v})
    out = runs_lib.check_regressions([mk(1000.0), mk(1000.0), mk(500.0)])
    assert out["failures"] == []
    assert any("GOOD direction" in n and "peak_hbm_bytes" in n
               for n in out["notes"])
    assert any(r["verdict"] == "improved" for r in out["rows"])
    # ...while GROWING past tolerance on the same metric does gate
    out = runs_lib.check_regressions([mk(1000.0), mk(1000.0), mk(1200.0)])
    assert len(out["failures"]) == 1 and "peak_hbm_bytes" in out["failures"][0]


def test_error_records_are_history_not_observations():
    """A dead round carrying a (bogus) metric must not drag the
    baseline or trip the gate."""
    runs = [_bench(100.0), _bench(100.0),
            _bench(1.0, error="OOM"),  # dead — excluded from series
            _bench(98.0)]
    out = runs_lib.check_regressions(runs)
    assert out["failures"] == []
    row = next(r for r in out["rows"] if r["metric"] == "throughput")
    assert row["n"] == 3  # the error record never entered the series


def test_baselines_are_per_topology():
    """dp8's history must not gate dp16's first real run."""
    runs = [_bench(100.0), _bench(100.0), _bench(100.0),
            _bench(40.0, fp="TPUv4:dp16")]  # different topology, slower
    out = runs_lib.check_regressions(runs)
    assert out["failures"] == []  # dp16 has no baseline of its own


# ---------------------------------------------------------------------------
# ingest backfill
# ---------------------------------------------------------------------------


def test_ingest_preserves_fields_and_dedupes(tmp_path):
    """BENCH_r05 (phase/retryable/probe_attempts=91) and a multichip
    round survive the trip into the ledger verbatim; re-ingesting adds
    nothing."""
    src = [shutil.copy(os.path.join(REPO, n), tmp_path)
           for n in ("BENCH_r05.json", "MULTICHIP_r03.json")]
    ledger = str(tmp_path / "runs.jsonl")
    added, skipped = runs_lib.ingest_paths(ledger, src)
    assert (added, skipped) == (2, 0)
    runs = runs_lib.load_runs(ledger)
    bench = next(r for r in runs if r["kind"] == "bench")
    orig = json.load(open(os.path.join(REPO, "BENCH_r05.json")))["parsed"]
    assert bench["source"] == "BENCH_r05.json"
    assert bench.get("phase") == orig.get("phase")
    assert bench.get("retryable") == orig.get("retryable")
    assert bench["probe_attempts"] == orig["probe_attempts"] == 91
    assert "probe_logs" not in json.dumps(bench)  # log tails stay out
    multi = next(r for r in runs if r["kind"] == "multichip")
    assert multi["n_devices"] and "error" not in multi  # ok round
    # idempotent by source basename
    assert runs_lib.ingest_paths(ledger, src) == (0, 2)
    assert len(runs_lib.load_runs(ledger)) == 2


def test_committed_ledger_is_clean(tmp_path):
    """The acceptance criterion's first half: ``--check`` on the
    repo's own history must pass.  The history is the kept round
    records at the root of the repo, ingested into a ledger here (no
    mirror of them is committed; the default ledger file appears with
    the first run on the chip that appends to it)."""
    import glob

    kept = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))
                  + glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")))
    ledger = str(tmp_path / "runs.jsonl")
    added, skipped = runs_lib.ingest_paths(ledger, kept)
    assert (added, skipped) == (len(kept), 0) and added >= 5
    runs = runs_lib.load_runs(ledger)
    assert {r["kind"] for r in runs} == {"bench", "multichip"}
    assert runs_lib.check_regressions(runs)["failures"] == []


# ---------------------------------------------------------------------------
# the trends CLI gate
# ---------------------------------------------------------------------------


def _trends():
    spec = importlib.util.spec_from_file_location(
        "trends", os.path.join(REPO, "bin", "trends.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trends_check_exit_codes(tmp_path, capsys):
    """The acceptance criterion's second half: ``--check`` exits 0 on
    clean history and 2 the moment an injected throughput regression
    lands."""
    trends = _trends()
    ledger = str(tmp_path / "runs.jsonl")
    for v in (100.0, 101.0, 99.0):
        runs_lib.append_run(ledger, _bench(v))
    assert trends.main(["--check", "--ledger", ledger]) == 0
    assert "no regressions" in capsys.readouterr().out
    # inject a regression: 80 vs median ~100 is past the 10% tolerance
    runs_lib.append_run(ledger, _bench(80.0))
    assert trends.main(["--check", "--ledger", ledger]) == 2
    assert "REGRESSION" in capsys.readouterr().out
    # a missing ledger is usage error 1, not a silent pass
    assert trends.main(["--check", "--ledger",
                        str(tmp_path / "absent.jsonl")]) == 1


def test_trends_ingest_cli(tmp_path, capsys):
    trends = _trends()
    shutil.copy(os.path.join(REPO, "BENCH_r05.json"), tmp_path)
    ledger = str(tmp_path / "runs.jsonl")
    pat = str(tmp_path / "BENCH_r*.json")
    assert trends.main(["--ledger", ledger, "--ingest", pat]) == 0
    assert "ingested 1 record(s)" in capsys.readouterr().out
    assert trends.main(["--ledger", ledger, "--ingest", pat]) == 0
    assert "1 skipped" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# postmortem merge
# ---------------------------------------------------------------------------


def test_postmortem_names_hard_death_and_merges_evidence(tmp_path):
    """A footer-less flight dump + a supervisor episode ledger merge
    into one timeline that names the death for what it was."""
    flight = str(tmp_path / "flight.jsonl")
    fr = FlightRecorder(flight, flush_every=1, fingerprint="fpX")
    for i in range(3):
        fr.record(step=i, loss=0.5)
    # no dump(): the process "died" here
    sup = str(tmp_path / "ledger.json")
    with open(sup, "w") as f:
        json.dump({"result": "crashed", "episodes": [
            {"n": 1, "class": "crashed", "rc": -9, "steps": 2,
             "wall_seconds": 1.0, "action": "restart_budget_exhausted"},
        ]}, f)
    text = runs_lib.postmortem_timeline(flight_path=flight,
                                        supervisor_ledger=sup)
    assert "fdtpu postmortem" in text
    assert "hard death" in text  # missing footer named as such
    assert "step=2" in text or '"step": 2' in text or "step 2" in text
    assert "crashed" in text
    assert text.strip().splitlines()[-1].startswith("verdict:")


def test_postmortem_with_clean_exit_reports_footer(tmp_path):
    flight = str(tmp_path / "flight.jsonl")
    fr = FlightRecorder(flight, flush_every=1)
    fr.record(step=0)
    fr.dump("done", steps=1)
    text = runs_lib.postmortem_timeline(flight_path=flight)
    assert "hard death" not in text
    assert "done" in text


# ---------------------------------------------------------------------------
# the run_info stitch gauge
# ---------------------------------------------------------------------------


def test_set_run_info_registers_labeled_gauge():
    reg = Registry()
    runs_lib.set_run_info(reg, "train", mode="spmd")
    text = reg.prometheus_text()
    assert "fdtpu_run_info{" in text
    assert 'component="train"' in text
    assert 'mode="spmd"' in text
    assert runs_lib.RUNS_SCHEMA in text  # schemas label stitches dumps
    # idempotent: a second call must not raise on re-registration
    runs_lib.set_run_info(reg, "train", mode="spmd")
