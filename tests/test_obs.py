"""repro.obs regression suite (ISSUE 10).

Pins the observability layer's contracts:

- **tracing**: span nesting, instant/counter events, Chrome trace export
  shape, and the scoped `use()` tracer swap;
- **zero-cost when disabled**: no tracer -> a span is a bare profiler
  annotation and records nothing, and an instrumented
  `ServingEngine.tick()` adds ZERO compiles to the serve step whether
  tracing is on or off (`_cache_size()`, as in test_qos.py);
- **metrics**: typed counters/gauges/histograms, the `_percentile` edge
  cases the serving stats lean on (empty/singleton/duplicates), and the
  BENCH_*.json `stamp()` schema;
- **timing**: the shared `measure()` helper that replaced the four
  hand-rolled timer loops (block_until_ready semantics, stat selection,
  value passthrough);
- **flight recorder**: ring capacity, `amend`, and the `trip` dump;
- **typed knob moves**: `KnobMove` reasons and the backward-compatible
  `knob_log` property;
- **A008**: the instrumentation-safety lint catches both known-bad modes
  (concretization inside jit; traced value escaping into a payload) and
  the tree itself lints clean.
"""
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs, qos
from repro.obs import metrics as obs_metrics
from repro.obs import recorder as obs_recorder
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.obs.timing import Measurement, measure


@pytest.fixture(autouse=True)
def _no_ambient_obs():
    """Every test starts with tracing disabled, fresh metrics, and no
    flight recorder (and cannot leak any of them into other tests)."""
    obs_trace.disable()
    obs_metrics.reset()
    obs_recorder.uninstall()
    yield
    obs_trace.disable()
    obs_metrics.reset()
    obs_recorder.uninstall()


# --------------------------------------------------------------------------
# trace
# --------------------------------------------------------------------------

def test_trace_disabled_is_null_and_records_nothing():
    assert not obs_trace.enabled()
    s1 = obs_trace.span("a", x=1)
    assert type(s1) is jax.profiler.TraceAnnotation, \
        "disabled fast path must be a bare profiler annotation"
    with s1:
        obs_trace.event("nope")
        obs_trace.counter("nope", 1)
    assert obs_trace.get_tracer() is None


def test_trace_spans_nest_and_export_chrome():
    t = obs_trace.Tracer()
    with obs_trace.use(t):
        with obs_trace.span("outer", k="v"):
            with obs_trace.span("inner"):
                pass
        obs_trace.event("marker", reason="x")
        obs_trace.counter("tokens", 3)
        obs_trace.counter("tokens", 2)
    assert len(t) == 5      # 2 spans + 1 instant + 2 counter samples
    doc = t.to_chrome()
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["ph"] == "X"
    assert by_name["inner"]["ph"] == "X"
    assert by_name["marker"]["ph"] == "i"
    assert by_name["marker"]["args"]["reason"] == "x"
    # inner completes first and nests inside outer's [ts, ts+dur)
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    # counters are cumulative
    cts = [e for e in evs if e["ph"] == "C"]
    assert [c["args"]["value"] for c in cts] == [3.0, 5.0]
    assert t.counter_value("tokens") == 5.0
    assert doc["otherData"]["schema"] == obs_trace.SCHEMA_VERSION


def test_spans_reach_the_profiler_with_and_without_a_tracer(tmp_path):
    """A span is a profiler annotation under its bare name whether or not
    a tracer buffers it; events and counters stay in the buffer."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("obs.bare", x=1):
            obs_trace.event("obs.event")
        with obs_trace.use(obs_trace.Tracer()):
            with obs_trace.span("obs.buffered", y=2):
                obs_trace.counter("obs.counter", 1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    names = {e.name for p in data.planes for line in p.lines
             for e in line.events}
    assert {"obs.bare", "obs.buffered"} <= names
    assert not any(n.startswith(("obs.event", "obs.counter")) for n in names)


def test_trace_use_restores_previous_tracer():
    t1, t2 = obs_trace.Tracer(), obs_trace.Tracer()
    obs_trace.enable(t1)
    with obs_trace.use(t2):
        assert obs_trace.get_tracer() is t2
        obs_trace.event("inner_only")
    assert obs_trace.get_tracer() is t1
    assert len(t2) == 1 and len(t1) == 0


def test_trace_save_roundtrip(tmp_path):
    t = obs_trace.Tracer()
    with obs_trace.use(t):
        with obs_trace.span("s", arr=[1, 2]):
            pass
    path = str(tmp_path / "trace.json")
    t.save(path)
    doc = json.load(open(path))
    assert doc["traceEvents"][0]["name"] == "s"
    assert doc["traceEvents"][0]["args"]["arr"] == [1, 2]


# --------------------------------------------------------------------------
# metrics (incl. the EngineStats percentile edge cases)
# --------------------------------------------------------------------------

def test_percentile_empty_is_none():
    assert obs_metrics.percentile([], 50) is None
    assert obs_metrics.percentile([], 99) is None


def test_percentile_singleton_and_duplicates():
    assert obs_metrics.percentile([3.5], 50) == pytest.approx(3.5)
    assert obs_metrics.percentile([3.5], 99) == pytest.approx(3.5)
    assert obs_metrics.percentile([2.0, 2.0, 2.0], 50) == pytest.approx(2.0)
    assert obs_metrics.percentile([2.0, 2.0, 2.0], 99) == pytest.approx(2.0)
    assert obs_metrics.percentile([1.0, 3.0], 50) == pytest.approx(2.0)


def test_engine_stats_latency_summary_before_any_completion():
    from repro.serving.scheduler import EngineStats
    s = EngineStats()
    assert s.ttft_p50 is None and s.ttft_p99 is None
    assert s.latency_p50 is None and s.latency_p99 is None
    summ = s.latency_summary()
    assert summ["requests"] == 0
    assert all(summ[k] is None for k in
               ("ttft_p50_s", "ttft_p99_s", "latency_p50_s",
                "latency_p99_s"))
    s.ttft_s.append(0.25)                 # singleton
    assert s.ttft_p50 == pytest.approx(0.25)
    assert s.ttft_p99 == pytest.approx(0.25)
    s.latency_s.extend([1.0, 1.0, 1.0])   # duplicates
    assert s.latency_p50 == pytest.approx(1.0)
    assert s.latency_p99 == pytest.approx(1.0)


def test_metrics_registry_types_and_snapshot():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.0)
    reg.gauge("g").set(7.0)
    h = reg.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    hs = snap["histograms"]["h"]
    assert hs["count"] == 4 and hs["min"] == 1.0 and hs["max"] == 4.0
    assert hs["p50"] == pytest.approx(2.5)
    with pytest.raises(ValueError):
        reg.gauge("c")        # cross-type name collision
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_metrics_stamp_schema():
    obs_metrics.registry().counter("x").inc()
    doc = obs_metrics.stamp({"metric": "m"})
    assert doc["metric"] == "m"
    assert doc["obs"]["schema"] == obs_metrics.SNAPSHOT_SCHEMA_VERSION
    assert doc["obs"]["metrics"]["counters"]["x"] == 1.0


def test_obs_count_facade_feeds_both_sinks():
    t = obs_trace.Tracer()
    with obs_trace.use(t):
        obs.count("hits")
        obs.count("hits", 2.0)
    assert obs_metrics.registry().counter("hits").value == 3.0
    assert t.counter_value("hits") == 3.0


# --------------------------------------------------------------------------
# timing.measure — the shared timer
# --------------------------------------------------------------------------

def test_measure_returns_value_and_times():
    calls = []

    def fn(a, b=0):
        calls.append(a + b)
        return a + b

    m = measure(fn, 2, b=3, warmup=1, repeats=3)
    assert isinstance(m, Measurement)
    assert m.value == 5
    assert len(calls) == 4                    # 1 warmup + 3 timed
    assert len(m.times) == 3
    assert m.seconds == sorted(m.times)[1]    # median
    assert measure(fn, 1, warmup=0, repeats=1).seconds >= 0.0


def test_measure_stats_and_device_values():
    x = jnp.arange(8.0)
    m_min = measure(jnp.sum, x, warmup=1, repeats=3, stat="min")
    assert m_min.seconds == min(m_min.times)
    m_mean = measure(jnp.sum, x, warmup=0, repeats=2, stat="mean")
    assert m_mean.seconds == pytest.approx(sum(m_mean.times) / 2)
    assert float(m_mean.value) == 28.0


def test_measure_emits_span_when_traced():
    t = obs_trace.Tracer()
    with obs_trace.use(t):
        measure(lambda: 1, warmup=0, repeats=2, span="unit.timer")
    names = [r["name"] for r in t.records]
    assert "unit.timer" in names


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------

def test_recorder_ring_amend_and_trip(tmp_path):
    rec = obs_recorder.FlightRecorder(capacity=3, out_dir=str(tmp_path))
    for i in range(5):
        rec.note(tick=i)
    assert [e["tick"] for e in rec.window()] == [2, 3, 4]
    rec.amend(knob=0.1)
    assert rec.window()[-1] == {"tick": 4, "knob": 0.1}
    dump = rec.trip("fallback", request_class="batch")
    assert dump["schema"] == obs_recorder.DUMP_SCHEMA_VERSION
    assert dump["reason"] == "fallback"
    assert dump["context"] == {"request_class": "batch"}
    assert [e["tick"] for e in dump["ticks"]] == [2, 3, 4]
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 1 and "fallback" in files[0]
    on_disk = json.load(open(tmp_path / files[0]))
    assert on_disk["ticks"] == dump["ticks"]
    # ring survives the trip (a second fault dumps overlapping context)
    assert len(rec.window()) == 3 and len(rec.dumps) == 1


def test_recorder_install_uninstall():
    assert obs_recorder.get_recorder() is None
    rec = obs_recorder.install(capacity=4)
    assert obs_recorder.get_recorder() is rec
    obs_recorder.uninstall()
    assert obs_recorder.get_recorder() is None


# --------------------------------------------------------------------------
# report CLI
# --------------------------------------------------------------------------

def test_report_renders_trace_and_metrics(tmp_path, capsys):
    t = obs_trace.Tracer()
    with obs_trace.use(t):
        with obs_trace.span("alpha"):
            pass
        obs_trace.event("beta", reason="r")
        obs_trace.counter("gamma", 2.0)
    path = str(tmp_path / "t.json")
    t.save(path)
    assert obs_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "alpha" in out and "gamma" in out

    obs_metrics.registry().histogram("h").observe(1.0)
    mpath = str(tmp_path / "m.json")
    with open(mpath, "w") as f:
        json.dump(obs_metrics.stamp({"metric": "x"}), f)
    assert obs_report.main([mpath]) == 0
    assert "h" in capsys.readouterr().out


# --------------------------------------------------------------------------
# serving integration: typed knob moves + zero extra compiles
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def decode_setup():
    from repro.models import build
    cfg = qos.default_decode_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, gen=6, cls="default"):
    from repro.serving import Request
    rng = np.random.RandomState(0)
    return [Request(uid=i,
                    prompt=rng.randint(0, cfg.vocab_size, 8)
                    .astype(np.int32),
                    max_new_tokens=gen, qos_class=cls)
            for i in range(n)]


def test_knob_reason_classification():
    from repro.serving.scheduler import ServingEngine
    import types
    eng = types.SimpleNamespace(qos=None)
    reason = ServingEngine._knob_reason
    assert reason(eng, 0.1, None) == "init"
    assert reason(eng, 0.0, 0.3) == "tighten"
    assert reason(eng, 0.3, 0.0) == "loosen"
    assert reason(eng, (0.1, 0.3), (0.3, 0.1)) == "mixed"
    assert reason(eng, (0.1,), (0.1, 0.3)) == "init"   # resharding edge
    fb = types.SimpleNamespace(in_fallback=True)
    eng_fb = types.SimpleNamespace(
        qos=types.SimpleNamespace(controllers={"default": fb}))
    assert reason(eng_fb, 0.0, 0.3) == "fallback"


def test_knob_events_typed_and_knob_log_compatible(decode_setup):
    from repro.serving import ServingEngine
    from repro.serving.scheduler import KnobMove
    cfg, model, params = decode_setup
    engine_qos = qos.QosEngine(
        serving_policy(), {"default": 0.5}, sample_fraction=1.0, window=4,
        config=qos.ControllerConfig(min_samples=1, hold_ticks=1))
    eng = ServingEngine(model, params, slots=2, max_len=32, prompt_len=8,
                        qos=engine_qos)
    for r in _requests(cfg, 2, gen=8):
        eng.submit(r)
    eng.run_until_drained()
    assert eng.knob_events, "the QoS loop must actuate at least once"
    assert all(isinstance(m, KnobMove) for m in eng.knob_events)
    assert eng.knob_events[0].reason == "init"
    assert eng.knob_events[0].previous is None
    for prev_m, m in zip(eng.knob_events, eng.knob_events[1:]):
        assert m.previous == prev_m.value
        assert m.reason in ("tighten", "loosen", "fallback", "mixed",
                            "init")
    # backward-compatible view: exactly the old (tick, value) tuples
    assert eng.knob_log == [(m.tick, m.value) for m in eng.knob_events]
    assert all(isinstance(t, int) for t, _ in eng.knob_log)


def serving_policy(metric="mape"):
    """Knob-backed ladder matching default_decode_cfg's structural params
    (hSize=2, pSize=4) without paying for a calibration sweep -- same
    shape as test_qos.py's helper."""
    from repro.core.harness import Record
    def rec(thresh, error, speedup):
        spec = {"technique": "taf", "level": "block", "hSize": 2,
                "pSize": 4, "thresh": thresh}
        return Record(app="toy", spec=spec, error=error, speedup=speedup,
                      modeled_speedup=speedup, approx_fraction=0.5,
                      wall_time_s=1.0, exact_time_s=1.0, extra={})
    return qos.QosPolicy.from_records(
        [rec(0.06, 0.02, 1.5), rec(0.3, 0.08, 3.0)],
        use_modeled=True, metric=metric)


def test_instrumented_tick_adds_zero_compiles(decode_setup):
    """The observability contract on the serving hot loop: spans, metrics
    and the flight recorder are host-side appends -- the jitted serve
    step's compile cache must not grow when tracing turns on/off."""
    from repro.serving import ServingEngine
    cfg, model, params = decode_setup
    eng = ServingEngine(model, params, slots=2, max_len=48, prompt_len=8)
    for r in _requests(cfg, 2, gen=24):
        eng.submit(r)
    eng.warmup()
    for _ in range(4):
        eng.tick()
    size0 = eng._serve._cache_size()

    t = obs_trace.Tracer()
    rec = obs_recorder.install(capacity=8)
    try:
        with obs_trace.use(t):
            for _ in range(4):
                eng.tick()
    finally:
        obs_recorder.uninstall()
    assert eng._serve._cache_size() == size0, \
        "tracing-enabled tick recompiled the serve step"
    names = {r_["name"] for r_ in t.records}
    assert "engine.tick" in names and "tick.serve" in names
    # no QoS plane -> nothing opens a flight note; the tick's amend() is
    # a no-op on the empty ring rather than inventing entries
    assert rec.window() == []

    for _ in range(4):                      # disabled again: still zero
        eng.tick()
    assert eng._serve._cache_size() == size0
    assert sum(r_["name"] == "engine.tick" for r_ in t.records) == 4, \
        "spans buffered only while tracing"


# --------------------------------------------------------------------------
# A008 instrumentation-safety lint
# --------------------------------------------------------------------------

def test_a008_catches_payload_tracer_leak():
    from repro.analysis.rules import check_instrumentation_safety

    def bad(x):
        obs_trace.event("knob", value=jnp.sum(x))   # traced value escapes
        return x * 2

    fs = check_instrumentation_safety(bad, (jnp.ones(4),), "unit.bad")
    assert any(f.severity.name == "ERROR" for f in fs)
    assert any("traced value" in f.message for f in fs)


def test_a008_catches_concretization():
    from repro.analysis.rules import check_instrumentation_safety

    def bad(x):
        obs_trace.event("knob", value=float(jnp.sum(x)))  # forced sync
        return x * 2

    fs = check_instrumentation_safety(bad, (jnp.ones(4),), "unit.sync")
    assert len(fs) == 1 and fs[0].severity.name == "ERROR"
    assert "concretizes" in fs[0].message


def test_a008_clean_function_passes():
    from repro.analysis.rules import check_instrumentation_safety

    def good(x):
        obs_trace.event("knob", value=0.1, reason="loosen")  # host scalars
        return x * 2

    assert check_instrumentation_safety(good, (jnp.ones(4),),
                                        "unit.good") == []


def test_a008_tree_lints_clean():
    """Meta-test: the repo's own instrumentation must satisfy its own
    lint (kernel targets; the decode target is covered by the full lint
    benchmark, which the regression baseline pins to zero findings)."""
    from repro.analysis.lint import run_lint
    rep = run_lint(apps=("kernels",), rules=("A008",))
    assert rep.errors == []
    bad = [f for f in rep.findings if f.severity.name == "ERROR"]
    assert bad == [], f"A008 findings on the tree: {bad}"
