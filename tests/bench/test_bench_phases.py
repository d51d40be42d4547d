"""The split of the engine's host and device time by its own spans
(bench/trace/phases.py) and the readers built on it: on hand-made events
whose answers are worked out by hand, on traces recorded on the chip, and
on a trace of the smoke QoS engine taken on the CPU."""
import os

import pytest

from bench import cells, harness
from bench.trace import phases, xplane

MS = 1_000_000     # ns
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
QOS_FIXTURE = os.path.join(FIXTURES, "qos")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW_READERS = ("readback_ms_per_tick", "canary_host_ms_per_tick",
               "qos_host_ms_per_tick", "taf_step_ms", "canary_step_ms")


def _ms(*spans):
    return [(n, s * MS, e * MS) for n, s, e in spans]


def _events():
    # before the window: the warm-up dispatched one serve step
    host = _ms(("engine.warmup", -20, -10),
               ("PjitFunction(serve_step)", -15, -14),
               ("PjitFunction(serve_step)", -14.9, -14.1),
               # window: an admission [0, 10] and two ticks [10, 40], [40, 60]
               ("bench.admit", 0, 10), ("bench.tick", 10, 40),
               ("bench.tick", 40, 60),
               ("engine.admit", 1, 9),
               ("PjitFunction(prefill_step)", 1.5, 2),
               ("PjitFunction(prefill_step)", 1.6, 1.9),
               ("engine.tick", 11, 39),
               ("tick.serve", 12, 15),
               ("PjitFunction(serve_step)", 12.5, 14.5),
               ("PjitFunction(serve_step)", 12.6, 14.4),
               ("tick.canary", 15, 30),
               ("PjitFunction(serve_step)", 15.5, 17),
               ("PjitFunction(serve_step)", 15.6, 16.9),
               ("tick.host_read", 30, 36), ("tick.retire", 36, 38),
               ("engine.tick", 41, 59),
               ("tick.serve", 42, 45),
               ("PjitFunction(serve_step)", 42.5, 44),
               ("PjitFunction(serve_step)", 42.6, 43.9),
               ("tick.host_read", 45, 58))
    # the canary's run starts on the device before its dispatch ends and
    # after the serve run it follows: only the order matches them
    modules = _ms(("jit_serve_step(1)", -14, -12),
                  ("jit_prefill_step(2)", 2, 8),
                  ("jit_serve_step(1)", 14, 20),
                  ("jit_serve_step(3)", 20, 27),
                  ("jit_serve_step(1)", 44, 52))
    ops = _ms(("fusion.0", -14, -12), ("fusion.1", 2, 8),
              ("fusion.2", 14, 20), ("fusion.3", 20, 27),
              ("fusion.2", 44, 52))
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def _strip(ev):
    return dict(ev, host=[h for h in ev["host"]
                          if not h[0].startswith(phases.PROGRAM_PREFIXES)])


def test_innermost_names_each_piece_after_the_deepest_span():
    got = phases.innermost(_ms(("a", 0, 10), ("b", 2, 5), ("c", 3, 4),
                               ("d", 6, 8), ("e", 12, 14)))
    assert [(s / MS, e / MS, n) for s, e, n in got] == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 6, "a"),
        (6, 8, "d"), (8, 10, "a"), (12, 14, "e")]


def test_a_doubled_dispatch_counts_once():
    got = phases.outermost_dispatches(_events()["host"])
    assert [(p, t / MS) for p, t in got] == [
        ("serve_step", -15), ("prefill_step", 1.5), ("serve_step", 12.5),
        ("serve_step", 15.5), ("serve_step", 42.5)]


def test_phases_by_hand():
    p = phases.reduce_phases(_events())
    # engine.warmup lies outside the window
    assert set(p) == {"engine.admit", "engine.tick", "tick.serve",
                      "tick.canary", "tick.host_read", "tick.retire"}
    assert {n: v["count"] for n, v in p.items()} == {
        "engine.admit": 1, "engine.tick": 2, "tick.serve": 2,
        "tick.canary": 1, "tick.host_read": 2, "tick.retire": 1}
    assert p["engine.tick"]["host_s"] == pytest.approx(0.046)
    assert p["tick.host_read"]["host_s"] == pytest.approx(0.019)
    # idle [0,2], [8,14], [27,44], [52,60]; [27,44] crosses canary
    # [27,30], host_read [30,36], retire [36,38], engine.tick [38,39] and
    # [41,42], serve [42,44]
    idle = {n: v["idle_s"] for n, v in p.items()}
    assert idle == pytest.approx({
        "engine.admit": 0.002, "engine.tick": 0.004, "tick.serve": 0.004,
        "tick.canary": 0.003, "tick.host_read": 0.012, "tick.retire": 0.002})
    # bench.admit [0,1], [9,10]; bench.tick [10,11], [39,41], [59,60]
    r = xplane.reduce_events(_events())
    assert sum(idle.values()) + 0.006 == pytest.approx(r["idle_s"])
    # the n-th dispatch ran the n-th run from the trace's start
    assert p["tick.serve"]["device_s"] == pytest.approx(
        {"serve_step": 0.014})
    assert p["tick.canary"]["device_s"] == pytest.approx(
        {"serve_step": 0.007})
    assert p["engine.admit"]["device_s"] == pytest.approx(
        {"prefill_step": 0.006})
    assert phases.step_device_s(p["tick.serve"]) + phases.step_device_s(
        p["tick.canary"]) == pytest.approx(
            r["programs"]["serve_step"]["device_s"])


def _same_reduction(full, bare):
    """Every key of the reduction is the same with and without program
    spans, but the idle gaps' names; their total is the same."""
    for k in full:
        if k != "idle_gaps":
            assert full[k] == bare[k], k
    assert sum(t for _, t in full["idle_gaps"]) == pytest.approx(
        sum(t for _, t in bare["idle_gaps"]))


def test_program_spans_change_no_key_of_the_reduction():
    ev = _events()
    full = xplane.reduce_events(ev)
    bare = xplane.reduce_events(_strip(ev))
    _same_reduction(full, bare)
    # named by their midpoints, [27,44] and [52,60] fall to bench.tick,
    # then wholly to tick.host_read, which `phases` gives 0.012 s of them
    assert dict(bare["idle_gaps"])["bench.tick"] == pytest.approx(0.031)
    assert dict(full["idle_gaps"])["tick.host_read"] == pytest.approx(0.025)


def _context(red, traffic, ticks):
    from conftest import SMOKE, TRAFFIC
    return harness.MetricContext(
        trace=red, counters={"ticks": ticks, "canary_ticks": ticks // 4,
                             "taf_skipped": 3, "taf_total": 4 * ticks},
        ticks=[{"live": 4, "pos": 10}] * ticks,
        admits=[{"requests": 4}], conf=dict(SMOKE["qwen3-smoke"]),
        traffic=TRAFFIC[traffic], peaks=cells.load_peaks(ROOT, "TPU v5 lite"),
        chips=1)


def _read(name, ctx):
    """The listed metric `name` as the harness reads it: by the reader of
    its quantity, the name up to its first `.`."""
    return harness.load_reader(ROOT, name.split(".")[0]).read(ctx)


@pytest.mark.parametrize("where", ["", "qos"])
def test_recorded_traces_read_the_same_without_program_spans(where):
    """Both traces recorded on the chip: with the program's spans taken
    out, every reduction key but the idle gaps' names is unchanged, and so
    is every per-layer metric the benchmark lists."""
    path = xplane.find_trace(os.path.join(FIXTURES, where))
    ev = xplane.load_events(path)
    full = xplane.reduce_events(ev)
    bare = xplane.reduce_events(_strip(ev))
    _same_reduction(full, bare)
    bench = cells.load_benchmark(ROOT)
    for traffic in ("smoke-precise", "smoke-qos"):
        for m in bench["per_layer"]:
            a = _read(m["name"], _context(full, traffic, full["ticks"]))
            b = _read(m["name"], _context(bare, traffic, bare["ticks"]))
            assert a == b, m["name"]


def test_reduce_recorded_qos_trace():
    """A trace recorded on a TPU v5e by
    bench/tools/record_qos_trace_fixture.py: the smoke QoS engine, the
    canary on a quarter of ticks."""
    ev = xplane.load_events(xplane.find_trace(QOS_FIXTURE))
    r = xplane.reduce_events(ev)
    p = phases.reduce_phases(ev)
    # each program's outermost dispatches match its runs one to one
    runs = {}
    for name, _, _ in ev["devices"]["/device:TPU:0"]["modules"]:
        prog = xplane.program_name(name)
        runs[prog] = runs.get(prog, 0) + 1
    calls = {}
    for prog, _ in phases.outermost_dispatches(ev["host"]):
        calls[prog] = calls.get(prog, 0) + 1
    assert calls == runs
    assert p["engine.tick"]["count"] == r["ticks"] > 8
    assert 0 < p["tick.canary"]["count"] <= r["ticks"] // 4 + 1
    # the step, and the position's `jnp.int32(pos)` conversion
    assert set(p["tick.serve"]["device_s"]) == {"serve_step",
                                               "convert_element_type"}
    assert phases.step_device_s(p["tick.serve"]) + phases.step_device_s(
        p["tick.canary"]) == pytest.approx(
            r["programs"]["serve_step"]["device_s"])
    assert sum(v["idle_s"] for v in p.values()) <= r["idle_s"] + 1e-12
    ctx = _context(dict(r, phases=p), "smoke-qos", r["ticks"])
    for name in NEW_READERS:
        assert _read(name, ctx) > 0, name


def test_new_readers_read_nothing_outside_their_cells():
    ev = _events()
    red = dict(xplane.reduce_events(ev), phases=phases.reduce_phases(ev))
    qos = _context(red, "smoke-qos", 2)
    assert _read("readback_ms_per_tick", qos) == pytest.approx(6.0)
    assert _read("canary_host_ms_per_tick", qos) == pytest.approx(1.5)
    assert _read("taf_step_ms", qos) == pytest.approx(7.0)
    assert _read("canary_step_ms", qos) == pytest.approx(7.0)
    # this hand-made trace holds no QoS spans
    assert _read("qos_host_ms_per_tick", qos) is None
    precise = _context(red, "smoke-precise", 2)
    assert _read("readback_ms_per_tick", precise) == pytest.approx(6.0)
    for name in NEW_READERS[1:]:
        assert _read(name, precise) is None, name
    # a trace without the program's spans (or a reduction without phases)
    bare = xplane.reduce_events(_strip(ev))
    for red in (bare, dict(bare, phases=phases.reduce_phases(_strip(ev)))):
        for name in NEW_READERS:
            assert _read(name, _context(red, "smoke-qos", 2)) is None, name


def test_engine_spans_reach_the_profiler(tmp_path):
    """On the CPU, under the benchmark's own profiler session: the
    engine's spans sit on the host line of the `bench.*` spans, each
    inside one, and there is one `tick.canary` per canary tick."""
    from conftest import make_bench_root
    from bench import traffic
    cell = ("qwen3-smoke", "smoke-qos")
    root = make_bench_root(tmp_path / "bench", [cell])
    conf = cells.load_config(root, cell[0])
    mix = cells.load_traffic(root, cell[1])
    params = cells.family(conf).program_params(
        0, conf, cells.program_config(conf, approx=False).padded_vocab_size)
    engine = harness.build_engine(root, conf, mix, params)
    engine.warmup()
    waves = traffic.waves(mix, 0, conf["vocab_size"])
    harness.warm_wave(engine, waves)
    trace_dir = str(tmp_path / "trace")
    win = harness.serve_window(engine, waves, 0.1, trace_dir=trace_dir)
    host = xplane.load_events(xplane.find_trace(trace_dir))["host"]
    bench = [h for h in host if h[0].startswith(xplane.SPAN_PREFIX)]
    spans = [h for h in host if h[0].startswith(phases.PROGRAM_PREFIXES)]
    names = {n for n, _, _ in spans}
    assert names == {"engine.admit", "engine.tick", "tick.actuate",
                     "tick.serve", "tick.canary", "tick.host_read",
                     "tick.retire", "tick.qos_update"}
    for _, s, e in spans:
        assert any(bs <= s and e <= be for _, bs, be in bench)
    count = {n: sum(1 for h in spans if h[0] == n) for n in names}
    assert count["engine.tick"] == win.counters["ticks"]
    assert count["tick.canary"] == win.counters["canary_ticks"] > 0
    assert count["engine.admit"] == sum(1 for h in bench
                                        if h[0] == "bench.admit")
