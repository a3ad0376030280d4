"""Read, on the chip and at a cell's own size, what the limits are set
from.  Not part of a benchmark run.

    python3 chipbench/calibrate.py --workload NAME --seeds 1,2,3 \
        [--control-seeds 3] [--memory] [--out chiprun_out/NAME.jsonl]

For each seed: the program's first steps through the timed path against
the float32 reference (the lower reading); for the first
``--control-seeds`` of them also the control (the reference with float8
operands) and each fault planted in the reference put in the program's
place: half of the batch left out, and on several chips the exchange
left out.  ``--memory`` prints, for the first seed, what the compiler
and the allocator say of the same step (PERF.md, "Memory").
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def memory_probe(task, devices, seconds: float) -> dict:
    import jax

    from chipbench import harness

    it = iter(task.loader)
    batch = next(it)
    it.close()
    compiled = task.step_fn.lower(task.state, batch).compile()
    ma = compiled.memory_analysis()
    out = {"compiler": {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(ma, k)}}
    del batch, compiled
    out["allocator_before"] = devices[0].memory_stats()
    seen = {"in_use": 0}
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            s = devices[0].memory_stats() or {}
            seen["in_use"] = max(seen["in_use"], s.get("bytes_in_use", 0))
            time.sleep(0.02)

    th = threading.Thread(target=poll, daemon=True)
    th.start()
    win = harness.window(task, seconds, None, {})
    stop.set()
    th.join()
    out["polled_max_bytes_in_use"] = seen["in_use"]
    out["allocator_after"] = devices[0].memory_stats()
    out["window"] = {"seconds": win["seconds"], "steps": win["steps"]}
    jax.block_until_ready(task.state)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--memory", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--program-only", action="store_true",
                    help="keep the program's first steps and the rows they "
                         "were fed; read no reference (a four-chip cell)")
    ap.add_argument("--from-program", default=None,
                    help="a --program-only file: read the references for it "
                         "on one chip")
    args = ap.parse_args(argv)

    import jax

    from chipbench import harness, reference

    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(1 if args.from_program else cell.chips)
    cache = os.path.join(ROOT, ".jax_cache")
    out_path = args.out or os.path.join(
        ROOT, "chiprun_out", f"calibrate_{cell.name}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    kept = {}
    if args.from_program:
        with open(args.from_program) as f:
            kept = {r["seed"]: r for r in map(json.loads, f)}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if args.from_program:
            from chipbench.pool import PoolDataset

            pool = PoolDataset(seed, cell.traffic["pool_rows"],
                               cell.config["image"], cell.config["num_classes"])
            first, wrong = kept[seed]["first"], kept[seed]["feed_mismatch"]
            batches = [(pool.images[r], pool.labels[r]) for r in kept[seed]["rows"]]
            row = {"cell": cell.name, "seed": seed}
        else:
            task, pool, first, fed = harness.set_up(cell, seed, devices, cache)
            row = {"cell": cell.name, "seed": seed,
                   "set_up_s": time.perf_counter() - t0}
            if args.memory and n == 0:
                row["memory"] = memory_probe(task, devices, 5.0)
            del task
            gc.collect()
            batches, wrong = harness.fed_rows(pool, fed)
            if args.program_only:
                row.update(first=first, feed_mismatch=wrong,
                           rows=[pool.rows_of(i).tolist() for i, _ in fed])
            del fed
        row["feed_mismatch"] = wrong
        if args.program_only:
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            print(f"seed {seed}: program's first steps kept, losses "
                  f"{first['losses']}", flush=True)
            continue
        key = harness.seed_key(seed)
        with jax.default_device(devices[0]):
            t1 = time.perf_counter()
            ref = reference.first_steps(cell.config, cell.ref, key, batches)
            row["reference_s"] = time.perf_counter() - t1
            row["program"] = reference.compare(first, ref, cell.ref)
            row["losses"] = {"program": first["losses"], "reference": ref["losses"]}
            row["worst_leaf"] = reference.worst_leaves(first, ref)
            row["leaves"] = {"all": len(ref["grad1"]),
                             "live": len(reference.live_leaves(ref["grad1"]))}
            if n < args.control_seeds:
                others = {"control_fp8": ("fp8", 1.0),
                          "fault_half_batch": ("f32", 0.5)}
                if cell.chips > 1:
                    others["fault_no_exchange"] = ("f32", 1.0 / cell.chips)
                for name, (mode, rows) in others.items():
                    got = reference.first_steps(cell.config, cell.ref, key,
                                                batches, mode=mode, rows_used=rows)
                    row[name] = reference.compare(got, ref, cell.ref)
        line = json.dumps(row, default=str)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
