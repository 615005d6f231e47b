#!/usr/bin/env python3
"""Split one traced window of a cell by the program's host spans.

    python3 bench/span_table.py --workload <cell> --seed <n> [--seconds <s>]

Drives the cell as ``bench/run.py --trace 1`` does (the same set-up,
window and reference check), then reads the profile with
``benchlib/spans.py`` and prints one JSON line: the window's end-to-end
numbers, the device idle split by ``repro.*`` / ``bench.*`` span,
``untraced`` and ``in_program`` (ms in all, and per job or per step; the
same for the idle gaps over ``LONG_GAP_S`` alone), the counts the
program's ``repro.api.report`` spans carry, and device ms per step of
each ``model_*`` scope. With no TPU it exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

# Longer than any gap between the ops of one call, shorter than the one
# host gap a call leaves in either cell (on a TPU v5e: about 16 ms a
# consensus job, 7 ms a training call).
LONG_GAP_S = 5e-3


def per_unit(cell, view: dict) -> tuple[str, float]:
    """("step", steps) for a training window, else ("job", jobs)."""
    if "steps" in view:
        return "step", float(view["steps"])
    return "job", view["rounds"] / cell.traffic["rounds"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from benchlib import compare, device, manifest, spans, trace

    cell = manifest.resolve(args.workload)
    try:
        devs = device.chips(cell.chips)
    except device.NoChip as e:
        print(f"span_table: {e}; refusing to run", file=sys.stderr)
        return 2
    trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        out = cell.kind().drive(cell, args.seed, args.seconds, trace_dir,
                                devs, T_START)
        profile = trace.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    summary = trace.reduce(profile, n_devices=cell.chips,
                           hlo_texts=out.hlo_texts)
    idle = spans.idle_by_span(profile, n_devices=cell.chips)
    long_idle = spans.idle_by_span(profile, n_devices=cell.chips,
                                   longer_than=LONG_GAP_S)
    unit, n = per_unit(cell, out.view)
    groups: dict[str, float] = {}
    for name, s in idle.items():
        key = name.rsplit(".", 1)[0] if name.startswith(
            (spans.PROGRAM_PREFIX, trace.SPAN_PREFIX)) else name
        groups[key] = groups.get(key, 0.0) + s
    model = spans.model_phase_s(profile, n_devices=cell.chips,
                                hlo_texts=out.hlo_texts)
    checks = compare.checks(out.numbers, {**cell.traffic["limits"],
                                          "window_compiles": 0})
    result = {
        "workload": cell.name, "seed": args.seed, "unit": unit,
        "units": n, "correct": compare.passed(checks),
        "metrics": out.metrics, "setup_s": out.setup_s,
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "idle_ms": {k: 1e3 * v for k, v in sorted(idle.items())},
        "idle_ms_per_unit": {k: 1e3 * v / n for k, v in sorted(idle.items())},
        "idle_ms_per_unit_by_layer": {k: 1e3 * v / n
                                      for k, v in sorted(groups.items())},
        "long_gap_ms_per_unit": {k: 1e3 * v / n
                                 for k, v in sorted(long_idle.items())},
        "counts": spans.report_counts(profile),
        "model_ms_per_step": ({k: 1e3 * v / n for k, v in model.items()}
                              if unit == "step" else {}),
        "phase_ms_per_unit": {k: 1e3 * v / n
                              for k, v in summary.phase_s.items()},
        "breakdown": summary.breakdown(),
        "device": out.device,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
