"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cell, its configuration and traffic
are found by name through BENCHMARK.json (see bench/cells.py). With
`--trace 0` the result holds the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics from a profiler trace of the window.
The numbers that decide `correct` are printed last on standard error and
under `checks`, the result's last key.

Exit codes: 0 with a result line; 3 when the devices cannot run the cell
(no TPU, fewer chips than the cell asks for, a device kind missing from
bench/peaks.json); 1 on any other failure. Only exit 0 prints a result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the compile cache lives in the checkout, at a fixed path (its path is
# part of every entry's key); set before JAX is imported
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness

    try:
        result = harness.run_cell(ROOT, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START,
                                  log=log)
    except harness.NoChip as e:
        log(f"bench: {e}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
