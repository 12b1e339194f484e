"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The last line of standard output is the
result as one JSON object; the numbers compared with the reference are the
last lines of standard error.  Exits 2, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# The compile cache sits at a fixed path inside the checkout (git-ignored);
# TPU compiler logs go nowhere.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    bench = harness.load_benchmark()
    cell, config, traffic = harness.resolve(bench, args.workload)
    return harness.run_cell(cell, config, traffic, args.seed, args.seconds,
                            bool(args.trace), T_PROCESS, bench)


if __name__ == "__main__":
    sys.exit(main())
