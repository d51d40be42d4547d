"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines. Usage:

  PYTHONPATH=src python -m benchmarks.run [--only fig6,kernel] [--jobs N]

``--jobs`` is threaded through to every module whose ``main`` accepts a
``jobs`` keyword (the sweep-based figures): it sets the harness's parallel
evaluation width (batched runner chunk size / thread-pool workers).
``--db`` points those modules at a persistent results database, making
re-runs resumable (cached specs are not re-executed).
``--substrate`` selects the execution substrate (host | pallas); a module
must be able to measure the named path -- see ``substrate_support()`` for
the per-module table (`ffn` dispatches through `repro.core.substrate`,
`kernel` is pallas-native, everything else host-only) -- so the flag can
never silently measure the wrong path. ``--artifacts`` names a directory for machine-readable
outputs (kernel_micro writes its structural numbers, its regression
summary ``BENCH_kernel.json``, and the autotuner's ``tuning_cache.json``;
qos_serving writes ``BENCH_qos.json``; approx_ffn_sweep writes
``BENCH_ffn.json``; costmodel validates the analytical predictor against
measured sweeps and writes ``BENCH_costmodel.json``).
``--predict`` switches predict-aware modules (currently `ffn`) into
cost-model pruned mode: only the predicted Pareto-front band of the grid
(<= 1/5 of it) is measured, and the module reports how much of the
committed full-grid front the pruned sweep recovers (writes
``BENCH_ffn_predict.json``, never the full-grid baseline artifact).
``--devices`` runs device-aware modules (currently `qos`) with the decode
data plane sharded over that many devices (pair with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on a 1-GPU/CPU
host).
A module that raises prints ``<name>,ERROR,<msg>``; the other modules
still run, and the process then exits 1.
``--check-regression <baseline-dir-or-file>`` compares the artifacts
produced THIS run against committed baselines (benchmarks/baselines/) and
exits non-zero beyond the noise margin -- the CI perf gate. Structural
numbers (counts, fractions, hypervolumes) are held to a tight relative
tolerance; wall-clock throughputs only have to stay above
``(1 - noise) * baseline`` (default --noise 0.8, i.e. a 5x slowdown
fails: the gate exists to catch order-of-magnitude regressions like a
compile landing inside a timed region, not scheduler jitter across CI
hosts).
"""
from __future__ import annotations

import argparse
import glob
import inspect
import json
import os
import sys
import time

sys.path.insert(0, "examples")

from . import (approx_ffn_sweep, costmodel, fig3_table_memory,
               fig6_best_speedup, fig7_cg_sweep, fig8c_items_per_thread,
               fig10c_rsd_behavior, fig11c_hierarchy,
               fig12c_kmeans_convergence, kernel_micro, lint, obs_overhead,
               pareto_refine, qos_serving, roofline_table)

MODULES = {
    "fig3": fig3_table_memory,
    "fig6": fig6_best_speedup,
    "fig7": fig7_cg_sweep,
    "fig8c": fig8c_items_per_thread,
    "fig10c": fig10c_rsd_behavior,
    "fig11c": fig11c_hierarchy,
    "fig12c": fig12c_kmeans_convergence,
    "kernel": kernel_micro,
    "lint": lint,
    "ffn": approx_ffn_sweep,
    "pareto": pareto_refine,
    "qos": qos_serving,
    "roofline": roofline_table,
    "costmodel": costmodel,
    "obs": obs_overhead,
}


def substrate_support() -> dict:
    """Explicit --substrate support table: the substrates each module's
    measurements can come from. A module declaring a `substrate` parameter
    on its `main` dispatches through `repro.core.substrate` (host or
    pallas); kernel_micro is pallas-NATIVE (it times the Pallas kernels
    directly and cannot emulate the host path); everything else always
    runs the host technique emulation. The CLI fails fast whenever
    --substrate names a path a selected module cannot measure -- in
    EITHER direction, so the flag can never silently measure the wrong
    thing."""
    table = {key: {"host", "pallas"}
             if "substrate" in inspect.signature(mod.main).parameters
             else {"host"}
             for key, mod in MODULES.items()}
    table["kernel"] = {"pallas"}
    return table


# --------------------------------------------------------------------------
# regression gate: fresh artifacts vs committed baselines
# --------------------------------------------------------------------------

# Per-artifact check rules, by dotted path into the JSON:
#   exact    -- configuration identity: a mismatch means the benchmark is
#               no longer measuring the same thing as the baseline;
#   close    -- structural/quality numbers, deterministic up to float
#               rounding across hosts: |new - base| <= atol + rtol * |base|;
#   atleast  -- wall-clock throughputs: new >= (1 - noise) * base.
_BASELINE_CHECKS = {
    "BENCH_qos.json": {
        "exact": ("metric", "devices", "shards", "slots", "requests"),
        "close": ("measured_error", "fallback_rate",
                  "approx.taf_skip_fraction"),
        "atleast": ("precise.tokens_per_s", "approx.tokens_per_s"),
    },
    "BENCH_ffn.json": {
        "exact": ("substrate", "n_records", "parity.taf", "parity.iact",
                  "parity.perfo"),
        "close": ("front.n_front", "front.hypervolume", "front.best_error",
                  "front.best_speedup"),
        "atleast": (),
    },
    # approxlint must stay CLEAN, and the allowlist may only grow through
    # a reviewed baseline bump: a new finding, a crashed rule, or a new
    # allow entry all drift from the committed counts and fail the gate.
    "BENCH_lint.json": {
        "exact": ("metric", "summary.total", "summary.errors",
                  "summary.warnings", "summary.allowlisted"),
        "close": (),
        "atleast": (),
    },
    # kernel microbenchmarks: oracle/pipeline parity, recompile counts and
    # the tuned-beats-default verdict are deterministic (exact); the
    # data-dependent skip fractions are deterministic up to float rounding
    # (close); tuned-vs-default speedup ratios are wall-clock and only
    # have to stay above the noise margin (absolute microseconds are
    # machine-dependent and never gated).
    "BENCH_kernel.json": {
        "exact": ("metric", "substrate", "oracle_match.taf",
                  "oracle_match.iact", "sweep.n", "sweep.recompiles",
                  "pipeline_parity.taf_matmul",
                  "pipeline_parity.perforated_matmul",
                  "pipeline_parity.perforated_attention",
                  "tuning.all_beat_default"),
        "close": ("executed_grid_fraction.taf",
                  "executed_grid_fraction.iact"),
        "atleast": ("tuning.taf_matmul.speedup",
                    "tuning.iact_rowfn.speedup",
                    "tuning.perforated_matmul.speedup",
                    "tuning.perforated_attention.speedup"),
    },
    # the analytical predictor's validation: kept/dropped grid counts are
    # structural (exact); rank correlations and the pruned-sweep front
    # recovery are deterministic up to float rounding (close).
    "BENCH_costmodel.json": {
        "exact": ("apps.blackscholes.kept", "apps.blackscholes.bound_holds",
                  "apps.binomial_options.bound_holds",
                  "apps.lavamd.bound_holds",
                  "ffn.n_grid", "ffn.kept", "ffn.dropped",
                  "ffn.band_budget", "ffn.band_measured", "ffn.recovered"),
        "close": ("apps.blackscholes.spearman",
                  "apps.binomial_options.spearman", "apps.kmeans.spearman",
                  "apps.lavamd.spearman", "apps.minife_cg.spearman",
                  "ffn.spearman", "ffn.front_recovery.ratio"),
        "atleast": (),
    },
    # the obs layer's overhead contract: the 0.95-tracing-overhead floor
    # is gated as a precomputed boolean (`ratio_ok`) under `exact` --
    # `close` (rtol=0.25) and `atleast` (noise=0.8) are both far looser
    # than the contract -- and the disabled/enabled paths must add ZERO
    # compiles to the serve step (an instrumentation hook that changes a
    # jit signature is exactly the regression this file exists to catch).
    "BENCH_obs.json": {
        "exact": ("metric", "ratio_ok", "extra_compiles_disabled",
                  "extra_compiles_enabled"),
        "close": (),
        "atleast": ("disabled_ticks_per_s", "enabled_ticks_per_s"),
    },
}


def _lookup(doc, dotted):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def check_regression(artifacts_dir: str, baseline: str, *,
                     noise: float = 0.8, rtol: float = 0.25,
                     atol: float = 0.05) -> list:
    """Compare this run's artifacts against committed baselines. Returns a
    list of human-readable failure strings (empty = gate passed), ALWAYS
    covering every baseline file: an unreadable/corrupt artifact becomes a
    failure entry for that module and the scan continues, so one broken
    artifact cannot mask regressions in the modules after it. Every
    baseline file must have a fresh counterpart: a module silently dropped
    from the benchmark run is itself a regression."""
    if os.path.isdir(baseline):
        base_files = sorted(glob.glob(os.path.join(baseline,
                                                   "BENCH_*.json")))
    else:
        base_files = [baseline]
    if not base_files:
        return [f"no BENCH_*.json baselines found under {baseline}"]
    failures = []
    for bf in base_files:
        name = os.path.basename(bf)
        af = os.path.join(artifacts_dir, name)
        rules = _BASELINE_CHECKS.get(name)
        if rules is None:
            failures.append(f"{name}: no check rules registered in "
                            f"benchmarks.run._BASELINE_CHECKS")
            continue
        if not os.path.exists(af):
            failures.append(f"{name}: baseline committed but no fresh "
                            f"artifact in {artifacts_dir} (module not run?)")
            continue
        try:
            with open(bf) as f:
                base = json.load(f)
        except (OSError, ValueError) as e:
            failures.append(f"{name}: baseline unreadable "
                            f"({type(e).__name__}: {e})")
            continue
        try:
            with open(af) as f:
                new = json.load(f)
        except (OSError, ValueError) as e:
            failures.append(f"{name}: fresh artifact unreadable "
                            f"({type(e).__name__}: {e})")
            continue
        for key in rules.get("exact", ()):
            b, n = _lookup(base, key), _lookup(new, key)
            if b != n:
                failures.append(f"{name}:{key}: expected {b!r}, got {n!r}")
        for key in rules.get("close", ()):
            b, n = _lookup(base, key), _lookup(new, key)
            if not isinstance(n, (int, float)) or not isinstance(
                    b, (int, float)):
                failures.append(f"{name}:{key}: non-numeric "
                                f"(base={b!r}, new={n!r})")
            elif abs(n - b) > atol + rtol * abs(b):
                failures.append(
                    f"{name}:{key}: {n:.6g} vs baseline {b:.6g} "
                    f"(tolerance atol={atol} rtol={rtol})")
        for key in rules.get("atleast", ()):
            b, n = _lookup(base, key), _lookup(new, key)
            if not isinstance(n, (int, float)) or not isinstance(
                    b, (int, float)):
                failures.append(f"{name}:{key}: non-numeric "
                                f"(base={b!r}, new={n!r})")
            elif n < (1.0 - noise) * b:
                failures.append(
                    f"{name}:{key}: {n:.6g} below {(1 - noise):.0%} of "
                    f"baseline {b:.6g} (noise margin {noise})")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module keys "
                    f"(default all: {','.join(MODULES)})")
    ap.add_argument("--jobs", type=int, default=1,
                    help="parallel evaluation width for sweep-based modules")
    ap.add_argument("--db", default=None,
                    help="path to a persistent sweep DB (enables resume)")
    ap.add_argument("--substrate", default=None, choices=["host", "pallas"],
                    help="execution substrate for kernel-aware modules")
    ap.add_argument("--artifacts", default=None,
                    help="directory for machine-readable outputs (JSON)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard device-aware modules (qos) over N devices")
    ap.add_argument("--check-regression", default=None, metavar="BASELINE",
                    help="after the run, compare --artifacts against this "
                    "baseline dir/file and exit non-zero on regression")
    ap.add_argument("--noise", type=float, default=0.8,
                    help="throughput noise margin for --check-regression "
                    "(fail below (1-noise)*baseline; default 0.8)")
    ap.add_argument("--predict", action="store_true",
                    help="cost-model pruned mode for predict-aware modules "
                    "(ffn: measure only the predicted front band, <= 1/5 of "
                    "the grid, and report recovery vs the committed front)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a Chrome/Perfetto trace of the whole run "
                    "(one span per module, plus every repro.obs span the "
                    "modules emit) and write it to this path")
    args = ap.parse_args()
    if args.check_regression and not args.artifacts:
        ap.error("--check-regression needs --artifacts (the gate compares "
                 "the artifacts THIS run writes)")
    keys = args.only.split(",") if args.only else list(MODULES)
    for key in keys:  # fail fast, before any module burns sweep time
        if key.strip() not in MODULES:
            ap.error(f"unknown module {key.strip()!r} "
                     f"(choose from: {','.join(MODULES)})")
    if args.substrate:
        # Fail fast (before any module burns sweep time) when the named
        # substrate is not what a selected module measures: a host-only
        # module would silently measure the host emulation under
        # --substrate pallas, and the pallas-native kernel module would
        # silently measure the kernels under --substrate host.
        support = substrate_support()
        deaf = sorted(k.strip() for k in keys
                      if args.substrate not in support[k.strip()])
        if deaf:
            ap.error(
                f"--substrate {args.substrate} cannot be honored by "
                f"{','.join(deaf)}: the flag would silently measure a "
                "different path. Per-module support: "
                + "; ".join(f"{k}={'|'.join(sorted(v))}"
                            for k, v in sorted(support.items())
                            if k in {x.strip() for x in keys}))

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    print("name,us_per_call,derived")

    def report(name: str, us, derived: str = ""):
        print(f"{name},{us},{derived}", flush=True)

    tracer = None
    if args.trace:
        from repro.obs import trace as obs_trace
        tracer = obs_trace.Tracer()
        obs_trace.enable(tracer)

    from repro.obs import metrics as obs_metrics

    failed = []
    for key in keys:
        mod = MODULES[key.strip()]
        accepted = inspect.signature(mod.main).parameters
        kw = {k: v for k, v in (("jobs", args.jobs), ("db_path", args.db),
                                ("substrate", args.substrate),
                                ("artifacts_dir", args.artifacts),
                                ("devices", args.devices),
                                ("predict", True if args.predict else None))
              if k in accepted and v is not None}
        # each module starts from a clean metrics registry, so the obs
        # snapshot stamped into its BENCH_*.json is that module's alone
        obs_metrics.reset()
        t0 = time.time()
        try:
            if tracer is not None:
                from repro.obs import trace as obs_trace
                with obs_trace.span(f"bench.{key.strip()}"):
                    mod.main(report, **kw)
            else:
                mod.main(report, **kw)
        except Exception as e:  # run the other modules, then fail
            report(key, "ERROR", str(e)[:200])
            failed.append(key.strip())
        report(f"_{key}_total_s", f"{time.time() - t0:.1f}")

    if tracer is not None:
        from repro.obs import trace as obs_trace
        obs_trace.disable()
        tracer.save(args.trace)
        report("trace", len(tracer), args.trace)

    if failed:
        print(f"benchmark modules FAILED: {','.join(failed)}",
              file=sys.stderr)
        sys.exit(1)

    if args.check_regression:
        # after the module loop, OUTSIDE the per-module exception guard:
        # the gate must fail the process, not become an ERROR row
        fails = check_regression(args.artifacts, args.check_regression,
                                 noise=args.noise)
        for f in fails:
            report("regression", "FAIL", f)
        if fails:
            # name the offending artifact:metric pairs on stderr too --
            # CI log scrapers (and humans skimming a red job) should not
            # have to fish the failure out of the CSV stream
            print(f"regression gate FAILED ({len(fails)} check(s)):",
                  file=sys.stderr)
            for f in fails:
                print(f"  {f}", file=sys.stderr)
            sys.exit(2)
        report("regression", "OK",
               f"artifacts match {args.check_regression} "
               f"(noise={args.noise})")


if __name__ == "__main__":
    main()
