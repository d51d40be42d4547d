"""The reduction from a profiler trace to device busy time, per-program
device time and idle gaps (bench/trace/xplane.py), on hand-made events
whose answers are worked out by hand, and on a trace recorded on the chip."""
import os

import pytest

from bench.trace import xplane

MS = 1_000_000     # ns


def _events():
    # window: bench.admit [0, 10] ms and two ticks [10, 30], [30, 50] ms
    host = [("bench.admit", 0, 10 * MS), ("bench.tick", 10 * MS, 30 * MS),
            ("bench.tick", 30 * MS, 50 * MS),
            ("PjitFunction(serve_step)", 10 * MS, 13 * MS),
            ("host_read", 25 * MS, 30 * MS)]
    ops = [("fusion.1", 2 * MS, 8 * MS),        # prefill
           ("fusion.2", 14 * MS, 20 * MS), ("fusion.3", 19 * MS, 25 * MS),
           ("fusion.2", 32 * MS, 44 * MS),
           ("fusion.9", 48 * MS, 60 * MS)]      # runs past the window
    modules = [("jit_prefill_step(7)", 2 * MS, 8 * MS),
               ("jit_serve_step(3)", 14 * MS, 25 * MS),
               ("jit_serve_step(3)", 32 * MS, 44 * MS),
               ("jit_other(1)", 48 * MS, 60 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_union_and_complement():
    assert xplane.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.union_length([]) == 0
    assert xplane.complement([(1, 2), (1.5, 3)], 0, 5) == [(0, 1), (3, 5)]
    assert xplane.complement([(0, 5)], 0, 5) == []


def test_program_names():
    assert xplane.program_name("jit_serve_step(123)") == "serve_step"
    assert xplane.program_name("prefill_step") == "prefill_step"
    assert xplane.op_name(
        "%fusion.12 = bf16[4,64]{1,0} fusion(bf16[4,64]{1,0} %p)") \
        == "fusion.12"


def test_self_times_leave_out_nested_ops():
    # a while loop [0, 10] holding ops [1, 3] (itself holding [2, 3]) and
    # [5, 6]; then a lone op [20, 25]
    t = xplane.self_times([("while", 0, 10), ("a", 1, 3), ("b", 2, 3),
                           ("c", 5, 6), ("d", 20, 25)])
    assert t == {"while": 7, "a": 1, "b": 1, "c": 1, "d": 5}


def test_reduce_by_hand():
    r = xplane.reduce_events(_events())
    assert r["window_s"] == pytest.approx(0.050)
    # busy: [2,8] + [14,25] + [32,44] + [48,50] = 6 + 11 + 12 + 2 ms
    assert r["busy_s"] == pytest.approx(0.031)
    assert r["idle_s"] == pytest.approx(0.019)
    assert r["ticks"] == 2
    assert r["programs"]["serve_step"]["runs"] == 2
    assert r["programs"]["serve_step"]["device_s"] == pytest.approx(0.023)
    assert r["programs"]["prefill_step"]["device_s"] == pytest.approx(0.006)
    assert r["programs"]["other"]["device_s"] == pytest.approx(0.002)
    # operations by program, each op's time less that nested inside it
    ops = dict(r["device_ops"])
    assert ops["serve_step/fusion.2"] == pytest.approx(0.018)
    assert ops["prefill_step/fusion.1"] == pytest.approx(0.006)
    assert ops["other/fusion.9"] == pytest.approx(0.002)
    # idle gaps, named by the shortest host event over their midpoint:
    # [0,2] admit; [8,14] (mid 11) serve dispatch; [25,32] (mid 28.5)
    # host_read; [44,48] tick
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.admit"] == pytest.approx(0.002)
    assert gaps["PjitFunction(serve_step)"] == pytest.approx(0.006)
    assert gaps["host_read"] == pytest.approx(0.007)
    assert gaps["bench.tick"] == pytest.approx(0.004)
    assert sum(gaps.values()) == pytest.approx(r["idle_s"])


def test_busy_is_averaged_over_devices():
    ev = _events()
    ev["devices"]["/device:TPU:1"] = {"ops": [("f", 0, 50 * MS)],
                                      "modules": []}
    r = xplane.reduce_events(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.031 + 0.050) / 2)


def test_no_span_or_no_device_is_an_error():
    ev = _events()
    with pytest.raises(ValueError):
        xplane.reduce_events({"devices": ev["devices"], "host": []})
    with pytest.raises(ValueError):
        xplane.reduce_events({"devices": {}, "host": ev["host"]})


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures")


def test_reduce_recorded_trace():
    """A trace recorded on a TPU v5e by bench/tools/record_trace_fixture.py
    (a smoke-size engine: one admission and 19 ticks in a 50 ms window):
    the line and program names the reduction looks for are there."""
    r = xplane.reduce_trace(FIXTURE)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"] == pytest.approx(0.05, rel=0.01)
    assert r["ticks"] == 19
    # one serve step per tick; the admission prefills each request alone
    assert r["programs"]["serve_step"]["runs"] == r["ticks"]
    assert r["programs"]["prefill_step"]["runs"] >= 4
    assert sum(t for _, t in r["idle_gaps"]) <= r["idle_s"] + 1e-12
    names = [n for n, _ in r["device_ops"]]
    assert all(" " not in n and n.split("/")[0] in r["programs"]
               for n in names)
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"]
