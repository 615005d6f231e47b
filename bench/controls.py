#!/usr/bin/env python3
"""Read the comparison's numbers for the control and for planted faults.

    python3 bench/controls.py --workload <cell> --seeds 11 12 13

The benchmark's own runs never run this. For each seed it puts the plain
reference, computed one precision step below what the configuration
states (the control), or with a fault planted in it, in the program's
place, and prints the numbers the cell's comparison would read against
the clean reference: one JSON line per (seed, variant). Each traffic kind
names its variants (``kinds/<kind>.py``, ``controls``). These readings,
with the program's own over a dozen seeds, set each limit in the traffic
file (see PERF.md).
"""
import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from benchlib import manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--jobs", type=int, default=2,
                    help="consensus jobs read per seed")
    ap.add_argument("--manifest", default=str(manifest.MANIFEST))
    args = ap.parse_args(argv)
    mpath = pathlib.Path(args.manifest)
    cell = manifest.resolve(args.workload, mpath, mpath.parent / "bench")
    kind = cell.kind()
    for seed in args.seeds:
        t0 = time.perf_counter()
        for name, numbers in kind.controls(cell, seed, jobs_per_seed=args.jobs):
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "variant": name, **numbers}), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
