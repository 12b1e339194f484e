"""The control of ``correct``, and the program's readings beside it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Runs the cell once per seed, in this one process, on the chip, and prints
per seed one JSON line: the program's numbers compared with the reference
and its ``correct``, and for each control the same numbers with the
control's readings put in the program's place, judged by the same rule
(``harness.judge``): a control has to come out not correct.  The controls
are the references in the nearest precision below the configuration's:
the analyzer reference in float32 against its float64 self, and the model
reference with int8 (and fp8) weights and activations, read at the token
it puts first, against the float32 reference.  The benchmark's own runs do
not run it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def verdicts(cell, config, traffic, seed: int, seconds: float):
    """The program's checks and ``correct``, and each control's, of one
    run of the cell."""
    from bench import harness
    ctx = harness.Context(cell, config, traffic, seed, seconds, False,
                          time.perf_counter())
    ctx.control = True
    try:
        res = harness.driver(traffic["driver"]).run(ctx)
    finally:
        ctx.close()
    return {"correct": harness.judge(res["checks"]), "checks": res["checks"],
            "controls": harness.control_verdicts(res)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell, config, traffic = harness.resolve(harness.load_benchmark(),
                                            args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        doc = verdicts(cell, config, traffic, seed, args.seconds)
        print(json.dumps(dict({"workload": args.workload, "seed": seed},
                              **doc)), flush=True)
        for name, c in doc["controls"].items():
            print(f"control {name} seed {seed}: correct {c['correct']}",
                  file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
