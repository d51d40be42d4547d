"""Read the engine's time split by its own spans, in one traced run of a
cell.

    python3 bench/tools/phase_readings.py --workload <cell> --seed <n> \
        [--seconds 50]

The run is the one `bench/run.py --trace 1` makes, with two additions the
benchmark does not make yet: the trace's reduction also holds `phases`
(bench/trace/phases.py), and the cell's per-layer metrics also hold those
of `METRICS` that list it, read by their files under bench/metrics/. Once
`xplane.reduce_events` holds `phases` and `METRICS` are entries of
BENCHMARK.json, the benchmark's own traced runs read the same.

Prints one JSON line: the run's result, the window's tick count and the
phases. Exits 1 when a metric found nothing to read, as on a program
whose spans do not reach the profiler; the tick count and phases are
printed all the same.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

QOS = ["qwen3-1.7b.qos-batch"]
PRECISE = ["qwen3-1.7b.precise-batch", "qwen1.5-4b.precise-prompt"]


def _metric(name, layer, workloads):
    # a metric of the QoS cell moves that cell's own `tokens_per_s.qos`
    moves = "tokens_per_s.qos" if workloads == QOS else "tokens_per_s"
    return {"name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": layer,
            "moves": moves, "workloads": workloads}


METRICS = [
    _metric("readback_ms_per_tick", "engine", PRECISE),
    _metric("readback_ms_per_tick.qos", "engine", QOS),
    _metric("canary_host_ms_per_tick", "qos", QOS),
    _metric("qos_host_ms_per_tick", "qos", QOS),
    _metric("taf_step_ms", "serve step", QOS),
    _metric("canary_step_ms", "qos", QOS),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import cells, harness
    from bench.trace import phases, xplane

    out = {"workload": args.workload, "seed": args.seed}

    def reduce_trace(log_dir, top=10):
        ev = xplane.load_events(xplane.find_trace(log_dir))
        red = xplane.reduce_events(ev, top)
        red["phases"] = out["phases"] = phases.reduce_phases(ev)
        out["ticks"] = red["ticks"]
        return red

    load_benchmark = cells.load_benchmark

    def with_metrics(root):
        bench = load_benchmark(root)
        bench["per_layer"] = bench["per_layer"] + METRICS
        return bench

    xplane.reduce_trace = reduce_trace
    cells.load_benchmark = with_metrics
    try:
        out["result"] = harness.run_cell(
            ROOT, args.workload, args.seed, args.seconds, True, T_START,
            log=lambda m: print(m, file=sys.stderr, flush=True))
    except harness.MissingMetric as e:
        out["missing"] = str(e)
    print(json.dumps(out), flush=True)
    return 0 if "result" in out else 1


if __name__ == "__main__":
    sys.exit(main())
