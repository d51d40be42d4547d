"""The on-chip benchmark: `python3 bench/run.py --workload <cell> ...`.

See BENCHMARK.json for the cells and metrics, and PERF.md for why."""
