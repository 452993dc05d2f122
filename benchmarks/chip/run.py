"""Chip benchmark entry point.

    python3 benchmarks/chip/run.py --workload laplace2d.solve \\
        --seed 7 --seconds 10 --trace 0

Runs one cell of ``BENCHMARK.json`` (found at the root of the checkout)
on the chips of this machine.  With ``--trace 0`` the last stdout line
carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window.  Every number that
decides ``correct`` is printed beside its limit, last on standard error
and under ``checks`` in the result line.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program is not in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent.parent
sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT, bench_dir: Path = BENCH_DIR,
         require_chip: bool = True) -> int:
    args = parse_args(argv)
    try:
        spec = harness.load_json(root / "BENCHMARK.json")
        cell, entry = harness.find_cell(spec, args.workload)
        config = harness.load_json(harness.config_path(root, entry))
        mix = harness.load_json(
            harness.traffic_path(cell["traffic"], bench_dir))
        result = harness.run_cell(
            workload=args.workload, cell=cell, config=config, mix=mix,
            metrics=harness.cell_metrics(spec, args.workload,
                                         bool(args.trace)),
            seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
            root=root, t_start=T_START, bench_dir=bench_dir,
            require_chip=require_chip)
    except (harness.BenchError, FileNotFoundError, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    harness.print_checks(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
