"""Deadline discipline of the step sweep.

``benchmarks/step_sweep.py`` runs one child process per configuration,
each alone on the chip, from a parent that never touches jax.  With an
already-passed deadline every child must be declined, quickly, without
any process ever initialising a backend.
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_step_sweep_stops_before_deadline():
    env = {**os.environ, "SWEEP_DEADLINE_EPOCH": "1", "SWEEP_PLATFORM": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "step_sweep.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-500:]
    # structural check, robust to config-list edits: every per-config
    # line declined, nothing measured.  (Skipped configs never reach the
    # results list, so the {"sweep": []} summary contributes no rows.)
    per_config = [
        ln for ln in p.stdout.splitlines()
        if '"config"' in ln and '"sweep"' not in ln
    ]
    assert per_config, p.stdout[-800:]
    assert all('"skipped: deadline"' in ln for ln in per_config), p.stdout[-800:]
    assert '"img_per_sec_per_chip"' not in p.stdout


def test_step_sweep_parent_never_imports_jax():
    """One process per chip: the sweep's parent may spawn children that
    each need the chip only because it imports nothing that could
    initialise a backend itself."""
    path = os.path.join(REPO, "benchmarks", "step_sweep.py")
    probe = (
        "import runpy, sys\n"
        f"runpy.run_path({path!r}, run_name='step_sweep')\n"
        "print('LOADED', [m for m in ('jax', 'jaxlib', 'bench', "
        "'fluxdistributed_tpu') if m in sys.modules])\n")
    p = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                       text=True, timeout=60)
    assert "LOADED []" in p.stdout, (p.stdout, p.stderr[-500:])
