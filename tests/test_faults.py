"""Fault-injection harness unit tests (fast tier).

The preemption-tolerance subsystem must be provable WITHOUT hardware or
real outages: these tests drive the injection registry, the retry
policy, backend acquisition, and the bench error-classification table
deterministically on the fake mesh (docs/robustness.md).
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time

import pytest

from fluxdistributed_tpu import faults
from fluxdistributed_tpu.obs import get_registry


# ---------------------------------------------------------------------------
# with_retries
# ---------------------------------------------------------------------------


def test_with_retries_recovers_from_transients():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return 42

    slept = []
    assert faults.with_retries(
        flaky, tries=5, backoff=0.01, sleep=slept.append) == 42
    assert calls["n"] == 3
    assert len(slept) == 2
    # exponential: second pause ~2x the first (plus bounded jitter)
    assert slept[1] > slept[0]


def test_with_retries_nonretryable_raises_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("a bug, not weather")

    with pytest.raises(ValueError):
        faults.with_retries(bad, tries=5, backoff=0.0, sleep=lambda s: None)
    assert calls["n"] == 1


def test_with_retries_exhaustion_raises_budget_exceeded():
    def always():
        raise OSError("persistently transient")

    with pytest.raises(faults.RetryBudgetExceeded) as ei:
        faults.with_retries(always, tries=3, backoff=0.0,
                            sleep=lambda s: None)
    assert isinstance(ei.value.__cause__, OSError)


def test_with_retries_budget_caps_total_time():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise OSError("transient")

    t0 = time.monotonic()
    with pytest.raises(faults.RetryBudgetExceeded):
        faults.with_retries(always, tries=100, backoff=0.05, budget=0.2)
    assert time.monotonic() - t0 < 2.0
    assert calls["n"] < 100


def test_with_retries_per_attempt_timeout():
    """A hanging attempt is bounded by ``timeout`` and classified as
    retryable (a wedged backend init, not a bug)."""
    calls = {"n": 0}

    def hang_once():
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(5)
        return "ok"

    t0 = time.monotonic()
    out = faults.with_retries(
        hang_once, tries=2, timeout=0.2, backoff=0.0, sleep=lambda s: None)
    assert out == "ok"
    assert time.monotonic() - t0 < 3.0


def test_with_retries_custom_classifier():
    def fails():
        raise KeyError("weird")

    with pytest.raises(KeyError):
        faults.with_retries(
            fails, tries=3, backoff=0.0, sleep=lambda s: None,
            retryable=lambda e: isinstance(e, OSError))


def test_with_retries_counters_land_in_registry():
    reg = get_registry()
    before = reg.value("fdtpu_fault_retries_total", "unit_counter")

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise OSError("transient")
        return 1

    faults.with_retries(flaky, tries=3, backoff=0.0, sleep=lambda s: None,
                        site="unit_counter")
    assert reg.value("fdtpu_fault_retries_total", "unit_counter") == before + 1


# ---------------------------------------------------------------------------
# FaultPlan
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_plan():
    yield
    faults.clear_plan()


def test_fire_is_noop_without_plan():
    faults.fire("step", index=0)
    faults.fire("loader", index=3)
    assert faults.param("local_devices") is None


def test_plan_fail_at_index_and_times():
    faults.install_plan(
        faults.FaultPlan().fail("loader", at=2, times=2))
    faults.fire("loader", index=0)  # wrong index: no trigger
    with pytest.raises(faults.FaultInjected):
        faults.fire("loader", index=2)
    with pytest.raises(faults.FaultInjected):
        faults.fire("loader", index=2)
    faults.fire("loader", index=2)  # budget spent


def test_backend_unavailable_then_recovers():
    faults.install_plan(faults.FaultPlan().backend_unavailable(2))
    devs = faults.acquire_backend(
        tries=3, timeout=None, backoff=0.0, sleep=lambda s: None)
    assert devs, "third attempt should see the real backend"


def test_from_spec_roundtrip_and_unknown_keys():
    plan = faults.FaultPlan.from_spec({
        "sigterm_at_step": 3,
        "loader_fail": {"at": 1, "times": 2},
        "backend_unavailable": 1,
        "params": {"local_devices": 4},
    })
    assert plan.params["local_devices"] == 4
    with pytest.raises(ValueError, match="unknown fault-plan keys"):
        faults.FaultPlan.from_spec({"sigsegv_at_step": 1})


def test_from_spec_generic_fail_entries():
    """The serve-side surface: the ``fail`` key addresses any
    site/action directly (router and replica fault harness)."""
    plan = faults.FaultPlan.from_spec({
        "fail": [{"site": "serve.tick", "at": 3, "times": 1},
                 {"site": "serve.dispatch", "times": 2,
                  "message": "router chaos"}],
    })
    faults.install_plan(plan)
    faults.fire("serve.tick", index=0)  # wrong index: no trigger
    with pytest.raises(faults.FaultInjected):
        faults.fire("serve.tick", index=3)
    for _ in range(2):
        with pytest.raises(faults.FaultInjected, match="router chaos"):
            faults.fire("serve.dispatch")
    faults.fire("serve.dispatch")  # budget spent
    reg = faults._metrics()
    assert reg["injected"].value("serve.tick") >= 1
    assert reg["injected"].value("serve.dispatch") >= 2


def test_from_spec_fail_entry_validation():
    with pytest.raises(ValueError, match="unknown fail-entry keys"):
        faults.FaultPlan.from_spec(
            {"fail": [{"site": "x", "when": 3}]})
    with pytest.raises(ValueError, match="needs a site"):
        faults.FaultPlan.from_spec({"fail": [{"at": 3}]})
    with pytest.raises(ValueError, match="unknown fault action"):
        faults.FaultPlan.from_spec(
            {"fail": [{"site": "x", "action": "segfault"}]})
    with pytest.raises(ValueError, match="delay must be >= 0"):
        faults.FaultPlan().fail("x", action="sleep", delay=-1)


def test_sleep_and_hang_actions_stall_then_return():
    faults.install_plan(
        faults.FaultPlan()
        .fail("slow_site", action="sleep", delay=0.05)
        .fail("hang_site", action="hang", delay=0.05))
    t0 = time.monotonic()
    faults.fire("slow_site")  # returns (slow, not raising)
    assert time.monotonic() - t0 >= 0.05
    t0 = time.monotonic()
    faults.fire("hang_site")  # explicit delay bounds the "hang" in tests
    assert time.monotonic() - t0 >= 0.05
    # with no delay a hang would stall for the documented default
    assert faults.HANG_DELAY_SECONDS >= 600


def test_value_actions_corrupt_fire_value_only():
    """nan/inf actions corrupt the OBSERVED value at fire_value sites
    (the guard's sentinel taps) and never trigger plain fire() — a
    value corruption without a value is meaningless."""
    import math

    faults.install_plan(faults.FaultPlan.from_spec({
        "fail": [{"site": "train.loss", "at": 2, "action": "nan"},
                 {"site": "train.grad", "action": "inf", "times": 2}],
    }))
    # plain fire() at a value site: no-op (would raise if matched)
    faults.fire("train.loss", index=2)
    # wrong index passes through untouched
    assert faults.fire_value("train.loss", 1.5, index=1) == 1.5
    assert math.isnan(faults.fire_value("train.loss", 1.5, index=2))
    # times budget then exhausts
    assert faults.fire_value("train.loss", 1.5, index=2) == 1.5
    for _ in range(2):
        assert math.isinf(faults.fire_value("train.grad", 0.7))
    assert faults.fire_value("train.grad", 0.7) == 0.7
    reg = faults._metrics()
    assert reg["injected"].value("train.loss") >= 1
    assert reg["injected"].value("train.grad") >= 2


def test_fire_value_noop_without_plan():
    assert faults.fire_value("train.loss", 3.25, index=0) == 3.25


def test_fire_value_delivers_side_effect_actions_too():
    """A raise planted on a sentinel site still raises through
    fire_value — the detection machinery itself can be failed."""
    faults.install_plan(
        faults.FaultPlan().fail("train.loss", message="sentinel chaos"))
    with pytest.raises(faults.FaultInjected, match="sentinel chaos"):
        faults.fire_value("train.loss", 1.0)


def test_value_action_from_spec_roundtrip_validation():
    with pytest.raises(ValueError, match="unknown fault action"):
        faults.FaultPlan().fail("x", action="nanify")
    plan = faults.FaultPlan.from_spec(
        {"fail": [{"site": "train.grad", "at": 5, "action": "inf"}]})
    faults.install_plan(plan)
    assert faults.fire_value("train.grad", 1.0, index=4) == 1.0
    assert faults.fire_value("train.grad", 1.0, index=5) == float("inf")


def test_serve_tick_site_fires_in_scheduler_step():
    """The scheduler's per-tick injection point: tick k raises inside
    step() — and the LMServer engine loop is built to survive exactly
    this (loop_errors counts it, serving continues)."""
    from fluxdistributed_tpu.serve import Scheduler
    from fluxdistributed_tpu.serve.testing import FakeLMEngine

    sched = Scheduler(FakeLMEngine(), max_queue=4)
    faults.install_plan(
        faults.FaultPlan.from_spec(
            {"fail": [{"site": "serve.tick", "at": 1}]}))
    sched.step()  # tick 0: clean
    with pytest.raises(faults.FaultInjected):
        sched.step()  # tick 1: injected
    sched.step()  # tick 2: clean again


def test_sigterm_fault_sets_signal_flag():
    """The deterministic preemption: plan fires SIGTERM at step k, a
    SignalFlag handler records it, the process survives."""
    faults.install_plan(faults.FaultPlan().sigterm_at_step(2))
    with faults.SignalFlag() as flag:
        for j in range(4):
            faults.fire("step", index=j)
            if flag.is_set():
                break
    assert flag.is_set()
    assert j == 2
    assert flag.reason == "sigterm"
    # handlers restored: SIGTERM is back to its previous disposition
    assert signal.getsignal(signal.SIGTERM) is not flag._handler


def test_signal_flag_programmatic_set():
    flag = faults.SignalFlag()
    assert not flag.is_set()
    flag.set()
    assert flag.is_set()
    assert flag.reason == "requested"


def test_signal_flag_install_off_main_thread_is_noop():
    out = {}

    def run():
        flag = faults.SignalFlag().install()
        out["installed"] = flag.installed
        flag.uninstall()

    t = threading.Thread(target=run)
    t.start()
    t.join()
    assert out["installed"] is False


# ---------------------------------------------------------------------------
# loader integration: transient assembly failures are retried
# ---------------------------------------------------------------------------


def test_loader_retries_injected_transients():
    import numpy as np

    from fluxdistributed_tpu import data_mesh
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.data.loader import PrefetchLoader

    faults.install_plan(faults.FaultPlan().loader_fail(at=1, times=2))
    loader = PrefetchLoader(
        SyntheticDataset(nsamples=32, nclasses=4, shape=(4, 4, 3)),
        data_mesh(), batch_size=8, cycles=3)
    items = list(loader)
    assert len(items) == 3  # batch 1 survived two injected failures
    # determinism: retried batch 1 equals a clean loader's batch 1
    faults.clear_plan()
    clean = list(PrefetchLoader(
        SyntheticDataset(nsamples=32, nclasses=4, shape=(4, 4, 3)),
        data_mesh(), batch_size=8, cycles=3))
    np.testing.assert_array_equal(
        np.asarray(items[1]["image"]), np.asarray(clean[1]["image"]))


def test_loader_gives_up_after_retry_budget():
    from fluxdistributed_tpu import data_mesh
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.data.loader import PrefetchLoader

    faults.install_plan(faults.FaultPlan().loader_fail(at=0, times=99))
    loader = PrefetchLoader(
        SyntheticDataset(nsamples=32, nclasses=4, shape=(4, 4, 3)),
        data_mesh(), batch_size=8, cycles=2, retries=1)
    with pytest.raises(RuntimeError, match="prefetch worker failed"):
        list(loader)


def test_loader_start_cursor_yields_same_tail():
    import numpy as np

    from fluxdistributed_tpu import data_mesh
    from fluxdistributed_tpu.data import SyntheticDataset
    from fluxdistributed_tpu.data.loader import PrefetchLoader

    def make(start=0):
        return PrefetchLoader(
            SyntheticDataset(nsamples=32, nclasses=4, shape=(4, 4, 3)),
            data_mesh(), batch_size=8, cycles=4, start=start)

    full = list(make())
    tail = list(make(start=2))
    assert len(full) == 4 and len(tail) == 2
    for a, b in zip(full[2:], tail):
        np.testing.assert_array_equal(
            np.asarray(a["image"]), np.asarray(b["image"]))
    with pytest.raises(ValueError, match="past the end"):
        list(make(start=5))


# ---------------------------------------------------------------------------
# bench error classification (pure table; the harness itself is slow-tier)
# ---------------------------------------------------------------------------


def _bench_mod():
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import bench

    return bench


def test_bench_retryable_classification():
    bench = _bench_mod()
    # the backend_init phase IS the unavailability being waited out
    assert bench.retryable_error("backend_init", "anything at all")
    # unavailable/timeout signatures: retryable in any phase
    assert bench.retryable_error("compile", "measurement subprocess timed out")
    assert bench.retryable_error("measure", "UNAVAILABLE: socket closed")
    assert bench.retryable_error(
        "build", "remote_compile: read body: response body closed")
    assert bench.retryable_error("measure", "subprocess timed out after 60s")
    # real failures: not retryable, INCLUDING deterministic
    # compile-phase code errors
    assert not bench.retryable_error(
        "build", "TypeError: build_step() got an unexpected keyword")
    assert not bench.retryable_error(
        "compile", "InvalidArgument: broken custom call in HLO")
    assert not bench.retryable_error(
        "measure", "AssertionError: loss is NaN")
    # the bench table and the faults default classifier share ONE
    # signature list — no drift
    from fluxdistributed_tpu.faults import UNAVAILABLE_SIGNATURES

    assert bench._unavailable_sigs() is UNAVAILABLE_SIGNATURES
