"""The benchmark's command:

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

One run of one cell on the machine it is started on.  The last line of
standard output is one JSON object; notes and the numbers compared go on
earlier lines and, at the very end, on standard error.  No TPU, fewer
chips than the cell asks for, an unknown device kind or a checkout
without the program is a non-zero exit and no result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        import fluxdistributed_tpu  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 3
    try:
        cell = harness.load_cell(args.workload)
        # the program's one cache rule: where JAX_COMPILATION_CACHE_DIR is
        # set the cache lives there; else at this fixed path of the checkout
        out = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            t_process=T_PROCESS,
            cache_dir=os.path.join(ROOT, ".jax_cache"))
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    notes = out.pop("notes")
    print("chipbench notes " + json.dumps(notes), flush=True)
    print(json.dumps(out), flush=True)
    for name, row in out["compared"].items():
        print(f"chipbench compared {name} value={row['value']!r} "
              f"limit={row['limit']!r}", file=sys.stderr)
    print(f"chipbench correct={out['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
