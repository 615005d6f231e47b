"""``bench/run.py``: run one cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each compared number beside its limit).
The same numbers close standard error. With no TPU, or fewer chips than the
cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

from benchlib import compare, device, manifest


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=str(manifest.MANIFEST),
                    help="manifest to read (default: BENCHMARK.json)")
    return ap.parse_args(argv)


def run_cell(args: argparse.Namespace, t_start: float, *,
             require_tpu: bool = True) -> dict:
    """Run the cell and return its result object (not yet printed)."""
    from benchlib.trace import load, reduce

    mpath = pathlib.Path(args.manifest)
    cell = manifest.resolve(args.workload, mpath, mpath.parent / "bench")
    devs = device.chips(cell.chips, require_tpu=require_tpu)
    drive = cell.kind().drive
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        out = drive(cell, args.seed, args.seconds, trace_dir, devs, t_start)
        checks = compare.checks(out.numbers, {**cell.traffic["limits"],
                                              "window_compiles": 0})
        result = {"correct": compare.passed(checks),
                  "attempted": out.attempted, "failed": out.failed}
        if args.trace:
            summary = reduce(load(trace_dir), n_devices=cell.chips,
                             hlo_texts=out.hlo_texts)
            view = {"summary": summary, "cell": cell,
                    "peaks": device.peaks(out.device["kind"]),
                    "window_s": summary.window_s, **out.view}
            metrics = {}
            for m in cell.per_layer:
                value = cell.reader(m["name"])(view)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
            result["metrics"] = metrics
            result["device"] = {**out.device, "busy_s": summary.busy_s,
                                "window_s": summary.window_s}
            result["breakdown"] = summary.breakdown()
        else:
            values = {**out.metrics, "setup_s": out.setup_s}
            result["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                             "unit": m["unit"]}
                                 for m in cell.end_to_end}
            result["device"] = out.device
        result["checks"] = checks
        return result
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(t_start: float, argv=None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args, t_start)
    except device.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
