"""Whole runs of the harness on the CPU at smoke size, on a benchmark
defined only in a temporary directory (conftest.make_bench_root): the
look for a chip is skipped, everything else of a run is driven, and the
float32 program must come out correct, while a broken serve step must
not."""
import os
import subprocess
import sys
import time

import pytest

from bench import cells, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 33 + 17          # above 32 bits, as the benchmark's seeds are


def _run(root, workload, hook=None, seconds=1.0, seed=SEED):
    return harness.run_cell(root, workload, seed, seconds, False,
                            time.perf_counter(), require_chip=False,
                            engine_hook=hook, log=lambda m: None)


@pytest.mark.parametrize("workload", [
    "qwen3-smoke.smoke-precise", "qwen3-smoke.smoke-qos",
    "qwen1.5-smoke.smoke-precise", "qwen1.5-smoke.smoke-qos"])
def test_sound_run_is_correct(bench_root, workload):
    r = _run(bench_root, workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert set(r["metrics"]) == {"tokens_per_s", "tpot_ms_p95", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["max_gap_std"]["value"] <= 1e-3


def test_new_metric_file_is_picked_up(tmp_path):
    """A per-layer metric is a reader file plus a BENCHMARK.json entry:
    `per_layer` finds it by name; a reader listed for the cell that finds
    nothing to read fails the run instead of leaving its metric out."""
    from conftest import make_bench_root
    root = make_bench_root(tmp_path, [("qwen3-smoke", "smoke-precise")],
                           per_layer=["device_idle_share"])
    with open(os.path.join(root, "bench", "metrics", "new_metric.py"),
              "w") as f:
        f.write("def read(ctx):\n    return ctx.trace['ticks'] * 2.0\n")
    bench = cells.load_benchmark(root)
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "tokens_per_s"})
    ctx = harness.MetricContext(
        trace={"ticks": 21, "window_s": 2.0, "busy_s": 1.5},
        counters={}, ticks=[], admits=[], conf={}, traffic={}, peaks={},
        chips=1)
    out = harness.per_layer(root, bench, "qwen3-smoke.smoke-precise", ctx)
    assert out == {"device_idle_share": {"value": 25.0, "unit": "%"},
                   "new_metric": {"value": 42.0, "unit": "ms"}}
    ctx.trace["window_s"] = 0.0
    with pytest.raises(harness.MissingMetric, match="device_idle_share"):
        harness.per_layer(root, bench, "qwen3-smoke.smoke-precise", ctx)


def test_cell_bound_metric_reads_its_quantity(tmp_path):
    """`tpot_ms_p95.qos` is `tpot_ms_p95` under a bound of its own: the
    name up to its first `.` names the quantity."""
    import json
    from conftest import make_bench_root
    root = make_bench_root(tmp_path, [("qwen3-smoke", "smoke-qos")])
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["end_to_end"].append({
        "name": "tpot_ms_p95.qos", "unit": "ms", "better": "lower",
        "bound": 0.09, "source": "host_clock",
        "workloads": ["qwen3-smoke.smoke-qos"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    m = _run(root, "qwen3-smoke.smoke-qos")["metrics"]
    assert m["tpot_ms_p95.qos"] == m["tpot_ms_p95"]


def test_cell_bound_per_layer_metric_reads_its_quantity(tmp_path):
    """A per-layer metric split by cell, `device_idle_share.qos`, is read
    by its quantity's reader, `bench/metrics/device_idle_share.py`."""
    from conftest import make_bench_root
    root = make_bench_root(tmp_path, [("qwen3-smoke", "smoke-qos")],
                           per_layer=["device_idle_share"])
    bench = cells.load_benchmark(root)
    bench["per_layer"] = [dict(m, name="device_idle_share.qos",
                               workloads=["qwen3-smoke.smoke-qos"])
                          for m in bench["per_layer"]]
    ctx = harness.MetricContext(
        trace={"ticks": 21, "window_s": 2.0, "busy_s": 1.5},
        counters={}, ticks=[], admits=[], conf={}, traffic={}, peaks={},
        chips=1)
    out = harness.per_layer(root, bench, "qwen3-smoke.smoke-qos", ctx)
    assert out == {"device_idle_share.qos": {"value": 25.0, "unit": "%"}}


def test_traced_run_profiles_the_window_start_only(bench_root, tmp_path,
                                                   monkeypatch):
    """The profiler covers the window's first `TRACE_SECONDS`; the window
    runs on untraced to its end, and the counters cover the traced ticks."""
    from bench import traffic as traffic_mod
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.5)
    conf = cells.load_config(bench_root, "qwen3-smoke")
    traffic = cells.load_traffic(bench_root, "smoke-precise")
    cfg = cells.program_config(conf, approx=False)
    params = cells.family(conf).program_params(SEED, conf,
                                               cfg.padded_vocab_size)
    engine = harness.build_engine(bench_root, conf, traffic, params)
    engine.warmup()
    waves = traffic_mod.waves(traffic, SEED, conf["vocab_size"])
    harness.warm_wave(engine, waves)
    t0 = time.perf_counter()
    win = harness.serve_window(engine, waves, 2.0, str(tmp_path / "trace"))
    traced = [t["traced"] for t in win.ticks]
    assert traced[0] and not traced[-1]
    assert traced == sorted(traced, reverse=True)
    last_traced = win.ticks[sum(traced) - 1]["stamp"] - t0
    assert 0.5 <= last_traced < 2.0
    assert win.ticks[-1]["stamp"] - t0 >= 2.0
    assert win.counters["ticks"] == sum(traced)
    assert os.listdir(tmp_path / "trace")


def test_unknown_device_kind_is_an_error(bench_root):
    with pytest.raises(KeyError, match="device_kind"):
        cells.load_peaks(bench_root, "TPU v99")


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing(bench_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "qwen3-1.7b.precise-batch", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_every_cell_names_files_that_exist():
    bench = cells.load_benchmark(ROOT)
    for w in bench["workloads"]:
        conf = cells.load_config(ROOT, w["config"])
        assert conf["name"] == w["config"]
        cells.load_traffic(ROOT, w["traffic"])
        assert cells.load_limits(ROOT, w["name"])["max_gap_std"]["limit"]
        if cells.load_traffic(ROOT, w["traffic"])["engine"] == "qos":
            doc = cells.load_policy_doc(ROOT, w["config"])
            assert "default" in doc["targets"]
    for m in bench["per_layer"]:
        reader = harness.load_reader(ROOT, m["name"].split(".")[0])
        assert hasattr(reader, "read")
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_per_layer_metrics_move_a_metric_their_cells_report():
    """Each per-layer metric moves one end-to-end metric, named in full,
    and every cell it is listed for reports that metric. A cell reports
    one metric per quantity: `tpot_ms_p95.qos` is the quantity
    `tpot_ms_p95` under a bound of its own."""
    bench = cells.load_benchmark(ROOT)
    names = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in names:
        reported = {m["name"] for m in harness.cell_metrics(
            bench, w, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2, w
        assert len({n.split(".")[0] for n in reported}) == len(reported), w
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        moved = set(e2e[m["moves"]].get("workloads", names))
        assert set(m.get("workloads", moved)) <= moved, m["name"]
