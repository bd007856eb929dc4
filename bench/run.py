#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are read from
`BENCHMARK.json` and the files under ``bench/`` it names (`bench/spec.py`).
Exits with code 3, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for. The compile cache is the program's own
(`launch/compile_cache.enable_compile_cache`), kept in ``bench/.jax_cache``
inside the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, spec
    cell = spec.load_cell(args.workload)
    harness.find_devices(cell.chips, require_chip=True)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"bench: compile cache {enable_compile_cache()}", file=sys.stderr)
    harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
