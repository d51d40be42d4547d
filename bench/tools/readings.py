"""Read the numbers a cell's correctness limits are set from.

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--control]

For each seed, in this one process: a whole run of the cell (set-up, a
short window at the cell's own load, the sample against the float32
reference), printing one JSON line with the program's checks and, with
`--control`, the same checks of the tokens the fp8 control puts first at
the same positions, and whether the control came out correct. The lower
reading of a limit is the program's largest over a dozen seeds or more,
the upper the control's smallest.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = harness.run_cell(ROOT, args.workload, seed, args.seconds, False,
                             t, control=args.control,
                             log=lambda m: print(m, file=sys.stderr))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"],
                          "checks": r["checks"],
                          "control": r.get("control"),
                          "metrics": r["metrics"],
                          "memory_peak_bytes":
                              r["device"]["memory_peak_bytes"],
                          "wall_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
