#!/usr/bin/env python3
"""Run one benchmark cell once on the chip; see ``benchlib/cli.py``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout, so only the first run of a cell there compiles; the program
# defers to this variable.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

if __name__ == "__main__":
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from benchlib.cli import main

    sys.exit(main(T_START))
