"""Each cell's code path end to end on the CPU at a tiny size, the
command's refusal to run without a TPU, `correct` coming out false when
the timed path is broken underneath, and a configuration, a cell and a
metric added as new files alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from chipbench_tiny import ROOT, harness, run_tiny, tiny_cell

CELLS = [w["name"] for w in harness.load_manifest()["workloads"]]


# every cell of the manifest on its own chips, and the ViT-L/16 cell
# data-parallel over four devices as well
RUNS = [(name, None) for name in CELLS] + [("vit_l16_b32_x1", 4)]


@pytest.mark.parametrize("name,chips", RUNS)
def test_cell_runs_end_to_end_and_is_correct(name, chips):
    out = run_tiny(name, seed=2 ** 31 + 7, chips=chips)
    assert list(out)[-1] == "compared"
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] == out["notes"]["steps"] > 0
    assert set(out["metrics"]) == {"images_per_s_per_chip", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == (chips or tiny_cell(name).chips)
    for row in out["compared"].values():
        assert row["limit"] is not None and row["value"] <= row["limit"]


def test_traced_run_reports_per_layer_metrics_and_leaves_out_the_device_ones():
    out = run_tiny("vit_l16_b32_x1", trace=True)
    # no TPU plane in a CPU trace: the device readers find nothing and are
    # left out; the host-clock and counter readers report
    assert {"compile_s", "window_compiles", "dispatch_ms", "h2d_ms",
            "data_wait_pct", "step_mfu_pct"} <= set(out["metrics"])
    assert not {"step_device_ms", "device_idle_pct", "allreduce_exposed_ms",
                "hbm_peak_gib"} & set(out["metrics"])
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert "trace_lines" in out["notes"]


def test_four_chips_shard_the_batch_over_four_devices():
    cell = tiny_cell("vit_l16_b32_x1", chips=4)
    task, pool, first, fed = harness.set_up(cell, 3, jax.devices()[:4], None)
    assert task.mesh.devices.size == 4
    it = iter(task.loader)
    batch = next(it)
    it.close()
    assert len({s.device for s in batch["image"].addressable_shards}) == 4
    assert len(first["losses"]) == 3 and first["steps"] == 3
    assert harness.fed_rows(pool, fed)[1] == 0


def test_command_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def _digest(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_a_configuration_a_cell_and_a_metric_are_added_as_new_files(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(root, "tests", "chipbench"))
    before = _digest(os.path.join(root, "chipbench"))
    bench = os.path.join(root, "chipbench")
    # a configuration: its file of sizes, its plain reference beside it
    with open(os.path.join(bench, "configs", "vit_l16.json")) as f:
        cfg = json.load(f)
    cfg.update(name="vit_b16", depth=12, dim=768, num_heads=12, mlp_dim=3072)
    cfg["model"] = {"factory": "vit_b16", "kwargs": cfg["model"]["kwargs"]}
    with open(os.path.join(bench, "configs", "vit_b16.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(bench, "configs", "vit_l16.py"),
                os.path.join(bench, "configs", "vit_b16.py"))
    # a traffic mix and the cell's limits: data files
    with open(os.path.join(bench, "traffic", "b64.json"), "w") as f:
        json.dump({"kind": "train_closed_loop", "global_batch": 64,
                   "pool_rows": 256, "buffersize": 5, "steps_per_call": 1,
                   "check_steps": 3, "trace_after_s": 4.0, "trace_for_s": 3.0}, f)
    with open(os.path.join(bench, "limits", "vit_b16_b64_x1.json"), "w") as f:
        json.dump({"limits": {"loss1_gap": 0.1, "loss_gap": 0.1, "grad_gap": 0.1,
                              "head_gap": 0.1, "update_gap": 0.1}}, f)
    # a per-layer metric: a reader of its own
    with open(os.path.join(bench, "metrics", "steps_per_s.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    w = ctx['window']\n"
                "    return w['steps'] / w['seconds'] if w['steps'] else None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["configs"].append({"name": "vit_b16", "source": "arXiv:2010.11929",
                         "file": "chipbench/configs/vit_b16.json",
                         "reduced": [], "why": "a test"})
    m["workloads"].append({"name": "vit_b16_b64_x1", "config": "vit_b16",
                           "traffic": "b64", "chips": 1, "why": "a test"})
    m["per_layer"].append({"name": "steps_per_s", "unit": "steps/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "trainer loop",
                           "moves": "images_per_s_per_chip",
                           "workloads": ["vit_b16_b64_x1"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)

    cell = harness.load_cell("vit_b16_b64_x1", root=root)
    assert cell.config["dim"] == 768 and cell.traffic["global_batch"] == 64
    assert cell.ref.forward_macs(cell.config) < 20e9
    assert "steps_per_s" in [x["name"] for x in cell.metrics["per_layer"]]
    # the new metric is read in its own cell only; old cells are as they were
    old = harness.load_cell("vit_l16_b32_x1", root=root)
    assert "steps_per_s" not in [x["name"] for x in old.metrics["per_layer"]]
    ctx = {"window": {"steps": 10, "seconds": 4.0}}
    assert harness.read_metric(root, m, "steps_per_s", ctx) == 2.5
    ctx = {"window": {"steps": 0, "seconds": 4.0}}
    assert harness.read_metric(root, m, "steps_per_s", ctx) is None
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
