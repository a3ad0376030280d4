"""Test harness: 8 virtual CPU devices.

The analog of the reference's fake-device story (test/single_device.jl:
121-151 — integer fake devices that work because ``@device!`` is a no-op
without CUDA): here the very same SPMD mesh code runs against
``--xla_force_host_platform_device_count=8`` CPU devices, so every
sharding/collective path is exercised on CI hardware.

The tests run on the CPU: ``force_host_devices`` forces that platform,
and must run before any test initializes a JAX backend.
"""

from fluxdistributed_tpu.mesh import force_host_devices

force_host_devices(8)

# The bench cross-run ledger (bench.append_run_record) defaults to
# benchmarks/hw/runs.jsonl in the checkout — a test run must never
# append to repo history.  Empty string disables (tests that exercise the ledger
# monkeypatch.setenv a tmp path over this).
import os  # noqa: E402

os.environ.setdefault("FDTPU_RUNS_LEDGER", "")
