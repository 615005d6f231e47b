"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,table4] [--steps N]
    PYTHONPATH=src python -m benchmarks.run --full      # paper-size grids
    PYTHONPATH=src python -m benchmarks.run --only protocol --record

Prints ``name,us_per_call,derived`` CSV rows. Paper-claim assertions run
inside each module; a failed claim fails the harness.

``--record`` appends one :class:`repro.obs.registry.RunRecord` per
gated suite (the six that write a tracked ``BENCH_*.json``) to the
cross-run history, so ``python -m repro.obs.registry check`` can gate
this run against the rolling-median baseline.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

# Suites whose modules write a tracked claim-of-record JSON — the ones
# the cross-run registry gates (repro.obs.registry.GATES keys match the
# "bench" field inside each file).
RECORDED = {
    "protocol": "BENCH_protocol.json",
    "net": "BENCH_net.json",
    "sparse": "BENCH_sparse.json",
    "obs": "BENCH_obs.json",
    "async": "BENCH_async.json",
    "wire": "BENCH_wire.json",
}


def _record(name: str, history: str) -> None:
    from repro.obs.registry import RunRecord, append_record

    repo_root = pathlib.Path(__file__).resolve().parent.parent
    payload = json.loads((repo_root / RECORDED[name]).read_text())
    record = RunRecord.from_bench(payload, source="bench")
    append_record(record, history)
    print(f"{name}/_recorded,0,history={history};bench={record.bench}",
          file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,fig3,fig4,fig5,table2,table3,"
                         "table4,protocol,net,sparse,obs,async,wire,"
                         "kernels,roofline")
    ap.add_argument("--steps", type=int, default=None,
                    help="override per-benchmark step counts (smoke: 20)")
    ap.add_argument("--full", action="store_true", help="paper-size grids")
    ap.add_argument("--record", action="store_true",
                    help="append a RunRecord per gated suite to --history")
    ap.add_argument("--history", default="BENCH_history.jsonl",
                    help="registry history path (with --record)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_async, bench_obs, bench_protocol,
                            bench_sparse, bench_wire, fig2_sensitivity,
                            fig3_ras, fig4_scale, fig5_audit,
                            fig_resilience, kernel_bench, roofline,
                            table2_accuracy, table3_real_vs_esti,
                            table4_time)

    suites = {
        "fig2": lambda: fig2_sensitivity.main(args.steps or 120),
        "fig3": lambda: fig3_ras.main(args.steps or 100),
        "fig4": lambda: fig4_scale.main(args.steps or 80),
        "fig5": lambda: fig5_audit.main(args.steps or 1500),
        "table2": lambda: table2_accuracy.main(args.steps or 250, args.full),
        "table3": lambda: table3_real_vs_esti.main(args.steps or 250),
        "table4": lambda: table4_time.main(args.steps or 150),
        "protocol": lambda: bench_protocol.main(args.steps),
        "net": lambda: fig_resilience.main(args.steps),
        "sparse": lambda: bench_sparse.main(args.steps),
        "obs": lambda: bench_obs.main(args.steps),
        "async": lambda: bench_async.main(args.steps),
        "wire": lambda: bench_wire.main(args.steps),
        "kernels": kernel_bench.main,
        "roofline": roofline.main,
    }
    wanted = args.only.split(",") if args.only else list(suites)

    print("name,us_per_call,derived")
    failed = []
    for name in wanted:
        t0 = time.time()
        try:
            for row in suites[name]():
                print(row)
        except AssertionError as e:
            failed.append((name, str(e)))
            print(f"{name}/CLAIM-FAILED,0,{e}")
        else:
            if args.record and name in RECORDED:
                _record(name, args.history)
        print(f"{name}/_suite,{(time.time()-t0)*1e6:.0f},wall={time.time()-t0:.1f}s",
              file=sys.stderr)
    if failed:
        raise SystemExit(f"claim failures: {failed}")


if __name__ == "__main__":
    main()
