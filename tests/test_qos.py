"""QoS control plane tests (docs/qos.md): policy ladder construction and
selection, canary monitor parity with the offline metrics, deterministic
feedback control incl. the hard precise fallback, per-tick lane grouping,
and the closed loop through the continuous-batching serving engine."""
import dataclasses
import json

import numpy as np
import pytest

import jax

from repro import qos
from repro.core import batching
from repro.core.harness import Record, mape, mcr, sweep
from repro.core.types import (ApproxSpec, Level, PerforationKind,
                              PerforationParams, TAFParams, Technique)


def taf_record(thresh, error, speedup, modeled=None, h=2, p=4):
    spec = {"technique": "taf", "level": "block", "hSize": h, "pSize": p,
            "thresh": thresh}
    return Record(app="toy", spec=spec, error=error, speedup=speedup,
                  modeled_speedup=modeled if modeled is not None else speedup,
                  approx_fraction=0.5, wall_time_s=1.0, exact_time_s=1.0,
                  extra={})


LADDER_RECORDS = [
    taf_record(0.05, 0.002, 1.2),
    taf_record(0.10, 0.010, 1.5),
    taf_record(0.20, 0.040, 2.2),
    taf_record(0.40, 0.200, 3.0),
    taf_record(0.15, 0.050, 1.1),   # dominated (more error, less speedup)
    taf_record(0.30, 0.300, 0.8),   # slower than precise: never a rung
]


def make_policy(**kw):
    return qos.QosPolicy.from_records(LADDER_RECORDS, **kw)


# ------------------------------------------------------------------ policy

def test_ladder_starts_precise_and_ascends():
    pol = make_policy()
    assert pol.entries[0].precise
    assert pol.entries[0].error == 0.0 and pol.entries[0].speedup == 1.0
    errs = [e.error for e in pol.entries]
    spds = [e.speedup for e in pol.entries]
    assert errs == sorted(errs) and spds == sorted(spds)
    # dominated + slower-than-precise rows never become rungs
    assert len(pol) == 5
    assert all(e.speedup > 1.0 for e in pol.entries[1:])


def test_select_is_best_speedup_under_error():
    pol = make_policy()
    assert pol.select(qos.QosTarget(0.05)) == 3     # err 0.04 < 0.05
    assert pol.select(0.011) == 2                   # strict: 0.010 < 0.011
    assert pol.select(0.010) == 1                   # 0.010 not < 0.010
    assert pol.select(1e-9) == 0                    # nothing fits -> precise
    choice = pol.choose(0.05)
    assert choice.index == 3
    json.dumps(choice.to_json())  # serializable deployment artifact


def test_ladder_prunes_dominated_entries_on_direct_construction():
    """The ladder invariant holds on EVERY construction path: a merged or
    hand-edited entry list with mutually-dominated rows is pruned, so the
    controller can never loosen onto a strictly-worse rung."""
    worse = qos.PolicyEntry(spec={"technique": "taf", "level": "block",
                                  "hSize": 2, "pSize": 4, "thresh": 0.15},
                            error=0.02, speedup=1.5, modeled_speedup=1.5)
    better = qos.PolicyEntry(spec={"technique": "taf", "level": "block",
                                   "hSize": 2, "pSize": 4, "thresh": 0.05},
                             error=0.01, speedup=2.0, modeled_speedup=2.0)
    pol = qos.QosPolicy([worse, better])        # worse: more error, slower
    assert [e.spec_hash for e in pol.entries[1:]] == [better.spec_hash]
    # and load() re-normalizes too
    path_free = qos.QosPolicy(pol.entries)
    assert len(path_free) == len(pol)


def test_policy_metric_mismatch_raises():
    pol = make_policy(metric="mape")
    with pytest.raises(ValueError, match="metric"):
        pol.select(qos.QosTarget(0.1, metric="mcr"))


def test_target_rejects_zero_and_negative_bounds():
    # est >= max_error is the violation test, so a 0 bound would flag
    # even bit-exact precise canaries (error 0.0) as violations
    for bad in (0.0, -0.1):
        with pytest.raises(ValueError, match="max_error"):
            qos.QosTarget(bad)
    qos.QosTarget(1e-12)                  # tiny-but-positive is fine


def test_policy_save_load_roundtrip(tmp_path):
    pol = make_policy(app="toy", use_modeled=True)
    path = str(tmp_path / "policy.json")
    pol.save(path)
    back = qos.QosPolicy.load(path)
    assert [e.to_json() for e in back.entries] == \
        [e.to_json() for e in pol.entries]
    assert (back.metric, back.app, back.use_modeled) == ("mape", "toy", True)
    assert back.select(0.05) == pol.select(0.05)


def test_policy_from_db_scopes_app(tmp_path):
    db = str(tmp_path / "db.json")
    rows = [r.to_json() for r in LADDER_RECORDS]
    rows.append(dict(rows[0], app="other", error=9.9))
    with open(db, "w") as f:
        json.dump(rows, f)
    pol = qos.QosPolicy.from_db(db, app="toy")
    assert all(e.error < 9.0 for e in pol.entries)
    with pytest.raises(ValueError, match="no rows"):
        qos.QosPolicy.from_db(db, app="missing")


def test_validate_ladder_knobs_rejects_structural_specs():
    skip_spec = {"technique": "perfo", "level": "element", "kind": "small",
                 "skip": 4, "fraction": 0.25, "herded": True}
    bad = qos.QosPolicy([qos.PolicyEntry(spec=skip_spec, error=0.01,
                                         speedup=2.0, modeled_speedup=2.0)])
    with pytest.raises(ValueError, match="traced quality knob"):
        qos.validate_ladder_knobs(bad)
    qos.validate_ladder_knobs(make_policy())  # knob-backed ladder passes


def test_spec_knob():
    assert qos.spec_knob(None) is None
    assert qos.spec_knob(ApproxSpec()) is None
    taf = ApproxSpec(Technique.TAF, Level.BLOCK, taf=TAFParams(2, 4, 0.3))
    assert qos.spec_knob(taf) == pytest.approx(0.3)


# ----------------------------------------------------------------- monitor

def test_monitor_error_matches_offline_metrics_bitwise():
    rng = np.random.RandomState(0)
    mon = qos.QualityMonitor(metric="mape", sample_fraction=1.0, window=8)
    errs = []
    for _ in range(5):
        a, b = rng.randn(3, 7), rng.randn(3, 7)
        err = mon.observe(a, b)
        assert err == mape(a, b)          # bit-for-bit: SAME function
        errs.append(err)
    assert mon.estimate() == float(np.mean(np.asarray(errs[-8:], np.float64)))

    mon2 = qos.QualityMonitor(metric="mcr", sample_fraction=1.0, window=8)
    x = rng.randint(0, 5, 20)
    y = rng.randint(0, 5, 20)
    assert mon2.observe(x, y) == mcr(x, y)


def test_monitor_sampling_deterministic_and_exact_rate():
    mon = qos.QualityMonitor(sample_fraction=0.25, window=4)
    hits = [i for i in range(100) if mon.should_sample()]
    assert len(hits) == 25
    gaps = np.diff(hits)
    assert set(gaps.tolist()) == {4}      # floor-crossings: evenly spaced
    mon2 = qos.QualityMonitor(sample_fraction=0.25, window=4)
    assert [i for i in range(100) if mon2.should_sample()] == hits
    # edge rates
    always = qos.QualityMonitor(sample_fraction=1.0, window=4)
    assert all(always.should_sample() for _ in range(10))
    never = qos.QualityMonitor(sample_fraction=0.0, window=4)
    assert not any(never.should_sample() for _ in range(10))


def test_monitor_window_and_drift():
    mon = qos.QualityMonitor(sample_fraction=1.0, window=4)
    for e in (1.0, 1.0, 1.0, 1.0):
        mon.inject(e)
    assert mon.estimate() == 1.0
    assert mon.drift() == 0.0             # flat window: zero RSD
    mon.inject(9.0)                       # evicts one 1.0 (window=4)
    st = mon.stats()
    assert st.window_size == 4 and st.samples == 5
    assert st.estimate == float(np.mean([1.0, 1.0, 1.0, 9.0]))
    assert st.drift > 0.5                 # spiky window: high RSD
    assert st.mean_error == float(np.mean([1.0] * 4 + [9.0]))
    assert st.last == 9.0
    # everything above came through the fault hook: genuine mean excludes it
    assert st.injected == 5 and st.genuine_mean_error == 0.0
    mon.observe(np.ones(4), np.full(4, 1.5))     # one genuine pair (err 0.5)
    st2 = mon.stats()
    assert st2.injected == 5 and st2.samples == 6
    assert st2.genuine_mean_error == 0.5


# -------------------------------------------------------------- controller

def ctl_config(**kw):
    base = dict(headroom=0.8, backoff=0.5, min_samples=2, hold_ticks=2,
                fallback_hold=3, drift_limit=10.0)
    base.update(kw)
    return qos.ControllerConfig(**base)


def run_loop(errors_per_update, target=0.05, **cfg_kw):
    """Drive a controller with a scripted canary stream; returns it."""
    pol = make_policy()
    mon = qos.QualityMonitor(sample_fraction=1.0, window=4)
    ctl = qos.QosController(pol, mon, target, ctl_config(**cfg_kw))
    for e in errors_per_update:
        if e is not None:
            mon.inject(e)
        ctl.update()
    return ctl


def test_controller_loosen_recovers_to_offline_choice():
    """Pressure tightens off the offline rung; sustained headroom loosens
    back -- but with the offline prior trusted (default), never onto a rung
    whose sweep-time error already violates the bound."""
    stream = [0.045, 0.045] + [0.0005] * 10
    ctl = run_loop(stream)
    events = [p.event for p in ctl.trajectory]
    assert events[0] == "warmup"          # min_samples gate
    assert "tighten" in events and "loosen" in events
    assert ctl.index == 3                 # back AT the offline select choice
    assert max(p.index for p in ctl.trajectory) == 3   # never beyond it
    # hold_ticks hysteresis: no two moves closer than 2 updates
    moves = [p.step for p in ctl.trajectory
             if p.event in ("loosen", "tighten")]
    assert all(b - a >= 2 for a, b in zip(moves, moves[1:]))


def test_controller_explores_past_offline_prior_when_told():
    explorer = run_loop([0.0005] * 8, trust_offline=False)
    assert explorer.index == len(explorer.policy) - 1
    trusting = run_loop([0.0005] * 8)     # default: pinned at the prior
    assert trusting.index == 3
    assert all(p.event != "loosen" for p in trusting.trajectory)


def test_controller_tightens_under_pressure():
    # start at rung 3 (select 0.05 -> err 0.04), push estimate into the
    # headroom band (0.8*0.05=0.04 < est < 0.05): steps ONE rung precise
    ctl = run_loop([0.045] * 4)
    assert ctl.trajectory[0].event == "warmup"
    tighten = [p for p in ctl.trajectory if p.event == "tighten"]
    assert tighten and tighten[0].index == 2
    assert ctl.violations == 0            # never a hard violation


def test_controller_hard_fallback_and_recovery():
    # scripted spike: clean, VIOLATION, then clean canaries again
    stream = [0.001, 0.001, 10.0, 0.0, 0.0, 0.0, 0.0, None, None, None,
              None, None, None]
    ctl = run_loop(stream, target=0.05)
    events = [p.event for p in ctl.trajectory]
    ifall = events.index("fallback")
    assert ctl.trajectory[ifall].index == 0          # hard: straight to 0
    # pinned precise through the cooldown that follows the violation
    assert "cooldown" in events[ifall:]
    for p in ctl.trajectory[ifall:ifall + 4]:
        assert p.index == 0
    assert ctl.violations >= 1
    assert 0.0 < ctl.fallback_rate < 1.0
    # deterministic: replaying the stream reproduces the trajectory exactly
    ctl2 = run_loop(stream, target=0.05)
    assert ctl2.trajectory == ctl.trajectory


def test_controller_drift_gate_blocks_loosening():
    # alternating errors: tiny mean (far under backoff) but huge RSD --
    # the drift gate must refuse to loosen on an estimate that noisy
    # (trust_offline off so the drift gate is the ONLY thing blocking)
    stream = [0.0001, 0.004] * 6
    ctl = run_loop(stream, target=0.05, drift_limit=0.5,
                   trust_offline=False)
    assert all(p.event != "loosen" for p in ctl.trajectory)


# ------------------------------------------------------------- group_lanes

def test_group_lanes_partitions_by_structure():
    t1 = ApproxSpec(Technique.TAF, Level.BLOCK, taf=TAFParams(2, 4, 0.1))
    t2 = ApproxSpec(Technique.TAF, Level.BLOCK, taf=TAFParams(2, 4, 0.3))
    t3 = ApproxSpec(Technique.TAF, Level.BLOCK, taf=TAFParams(3, 4, 0.2))
    lanes = [t1, None, t2, ApproxSpec(), t3]
    groups, precise = batching.group_lanes(lanes)
    assert precise == [1, 3]
    key12 = batching.static_key(t1)
    assert groups[key12] == ([0, 2], [pytest.approx(0.1),
                                      pytest.approx(0.3)])
    assert groups[batching.static_key(t3)][0] == [4]  # singletons kept


def test_group_lanes_rejects_structural_knobless_spec():
    skip = ApproxSpec(Technique.PERFORATION, perforation=PerforationParams(
        kind=PerforationKind.SMALL, skip=4))
    with pytest.raises(ValueError, match="traced quality knob"):
        batching.group_lanes([skip])


# ------------------------------------------------------------------ engine

def test_qos_engine_plan_tick_strictest_live_rung():
    pol = make_policy()
    eng = qos.QosEngine(pol, {"default": 0.05, "batch": 1.0},
                        sample_fraction=0.0)
    assert eng.controller("default").index == 3
    assert eng.controller("batch").index == 4
    assert eng.controller("unknown-class").index == 3   # falls to default
    plan = eng.plan_tick(["batch", "default", "batch"])
    assert plan.index == 3                               # strictest live
    assert plan.knob == pytest.approx(
        pol.entries[3].spec["thresh"])
    plan_b = eng.plan_tick(["batch"])
    assert plan_b.index == 4
    assert plan_b.n_groups == 1
    # precise-only plan: no knob
    tight = qos.QosEngine(pol, 1e-9, sample_fraction=0.0)
    assert tight.plan_tick(["default"]).knob is None


def test_qos_engine_requires_default_class():
    with pytest.raises(ValueError, match="default"):
        qos.QosEngine(make_policy(), {"interactive": 0.05})


def test_plan_tick_regime_change_preserves_violation_evidence():
    """The knob-regime window reset must never discard VIOLATION evidence:
    a fault injected between ticks survives a simultaneous class-mix
    change, so the very next update still fires the hard fallback."""
    eng = qos.QosEngine(make_policy(), {"default": 0.05, "batch": 1.0},
                        sample_fraction=1.0, window=4,
                        config=ctl_config(min_samples=1, hold_ticks=1))
    eng.plan_tick(["batch"])              # actuate batch's (loosest) rung
    eng.monitor.inject(10.0)              # fault lands before the mix flips
    plan = eng.plan_tick(["default", "batch"])   # strictest rung changes
    assert plan.index == eng.controllers["default"].index
    assert eng.monitor.window_size == 1   # evidence kept, not reset
    eng.update(["default", "batch"])
    for cls in ("default", "batch"):
        assert eng.controllers[cls].violations == 1
    # sub-violation evidence IS dropped on a regime change (documented)
    eng2 = qos.QosEngine(make_policy(), {"default": 0.05, "batch": 1.0},
                         sample_fraction=1.0, window=4,
                         config=ctl_config(min_samples=1, hold_ticks=1))
    eng2.plan_tick(["batch"])
    eng2.monitor.inject(0.001)            # headroom, not a violation
    eng2.plan_tick(["default", "batch"])
    assert eng2.monitor.window_size == 0


def test_qos_engine_concurrent_violation_not_swallowed():
    """Evidence is snapshotted once per update: the first class's fallback
    resets the shared window, but the OTHER live classes still judge the
    same tick's estimate -- a concurrent violation of their bound must
    register, whatever the class iteration order."""
    eng = qos.QosEngine(make_policy(), {"default": 0.05, "batch": 1.0},
                        sample_fraction=1.0, window=4,
                        config=ctl_config(min_samples=1, hold_ticks=1))
    eng.monitor.inject(5.0)               # violates BOTH bounds
    eng.update(["default", "batch"])
    for cls in ("default", "batch"):
        ctl = eng.controllers[cls]
        assert ctl.violations == 1 and ctl.index == 0
        assert ctl.trajectory[-1].event == "fallback"


def test_qos_engine_observe_decode_metrics():
    pol_mcr = qos.QosPolicy(make_policy().entries, metric="mcr")
    eng = qos.QosEngine(pol_mcr, 0.5, sample_fraction=1.0)
    logits_a = np.array([[0.1, 0.9], [0.8, 0.2]])
    logits_b = np.array([[0.2, 0.8], [0.1, 0.9]])   # one argmax differs
    err = eng.observe_decode(logits_a, logits_b)
    assert err == mcr(np.argmax(logits_a, -1), np.argmax(logits_b, -1))
    assert err == 0.5


@pytest.mark.parametrize("metric", ["mape", "mcr"])
def test_qos_engine_observe_qoi_scores_as_observe_decode(metric):
    """`observe_qoi` on the QoI the metric reads (token ids under "mcr",
    the logits under "mape") books the same errors, in the same places, as
    `observe_decode` / `observe_shard` on the logits."""
    rng = np.random.RandomState(3)
    pol = qos.QosPolicy(make_policy().entries, metric=metric)
    targets = {"default": 0.5, "batch": 1.0}
    for shards in (None, 2):
        by_logits, by_qoi = (qos.QosEngine(pol, targets, sample_fraction=1.0)
                             for _ in range(2))
        assert by_qoi.scores_tokens == (metric == "mcr")
        if shards:
            for eng in (by_logits, by_qoi):
                eng.enable_sharding(shards)
        for i in range(6):
            ex, ap = rng.randn(3, 5), rng.randn(3, 5)
            classes = ["default", "batch"][:1 + i % 2]
            shard = None if shards is None else i % shards
            if shard is None:
                err = by_logits.observe_decode(ex, ap, classes)
            else:
                err = by_logits.observe_shard(shard, ex, ap, classes)
            if metric == "mcr":
                ex, ap = np.argmax(ex, -1), np.argmax(ap, -1)
            assert by_qoi.observe_qoi(ex, ap, classes, shard=shard) == err
            assert err == {"mape": mape, "mcr": mcr}[metric](ex, ap)
        assert by_qoi.summary() == by_logits.summary()
        assert by_qoi._exposure == by_logits._exposure
        for cls, mon in by_qoi.class_monitors.items():
            assert mon.stats() == by_logits.class_monitors[cls].stats()
    with pytest.raises(ValueError, match="enable_sharding"):
        qos.QosEngine(pol, 0.5).observe_qoi(ex, ap, shard=0)


# --------------------------------------------- closed loop through serving

@pytest.fixture(scope="module")
def decode_setup():
    from repro.models import build
    cfg = qos.default_decode_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def serving_policy(metric="mape"):
    """Knob-backed ladder matching default_decode_cfg's structural params
    (hSize=2, pSize=4) without paying for a calibration sweep."""
    return qos.QosPolicy.from_records(
        [taf_record(0.06, 0.02, 1.5), taf_record(0.3, 0.08, 3.0)],
        use_modeled=True, metric=metric)


def _requests(cfg, n, gen=6, cls="default"):
    rng = np.random.RandomState(7)
    from repro.serving import Request
    return [Request(uid=i,
                    prompt=rng.randint(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=gen, qos_class=cls) for i in range(n)]


def test_serving_closed_loop_backs_off_and_recompiles_nothing(decode_setup):
    """The acceptance demo: seeded trace, injected error spike -> the
    controller provably falls back to precise (threshold AND in-flight
    predictions zeroed) and the end-to-end measured canary error stays
    under the configured target; knob moves never recompile the step."""
    from repro.serving import ServingEngine
    cfg, model, params = decode_setup
    # mcr canaries are bounded by 1.0, so a bound of 2.0 is unreachable by
    # genuine traffic: the injected fault is the ONLY violation source and
    # the trajectory is deterministic
    target = 2.0
    engine_qos = qos.QosEngine(
        serving_policy(metric="mcr"), target, sample_fraction=1.0, window=4,
        config=ctl_config(min_samples=1, hold_ticks=1, fallback_hold=3))
    eng = ServingEngine(model, params, slots=2, max_len=32, prompt_len=8,
                        qos=engine_qos)
    for r in _requests(cfg, 2, gen=10):
        eng.submit(r)
    ctl = engine_qos.controllers["default"]
    for _ in range(6):
        eng.tick()
    assert ctl.index > 0, "under a loose bound the approx knob stays open"
    engine_qos.monitor.inject(10.0)               # deterministic spike
    eng.tick()
    assert ctl.index == 0                         # hard precise fallback
    assert ctl.trajectory[-1].event == "fallback"
    eng.tick()                                    # fallback knob actuated
    taf = eng.cache["taf"]
    assert float(np.max(np.asarray(taf["threshold"]))) == 0.0
    assert int(np.asarray(taf["remaining"]).sum()) == 0
    stats = eng.run_until_drained()
    assert stats.finished == 2
    assert stats.canary_ticks == stats.ticks      # sample_fraction=1.0
    assert stats.knob_moves >= 2                  # opened, then fell back
    # ONE compiled serve step despite every knob move (traced threshold)
    assert eng._serve._cache_size() == 1
    # end-to-end measured error under the bound (spike included via mean)
    assert engine_qos.summary()["mean_error"] < target


def test_serving_precise_canaries_are_bit_exact(decode_setup):
    """With the knob pinned precise, the approx decode step and the exact
    oracle are the SAME computation: every canary error is exactly 0.0."""
    from repro.serving import ServingEngine
    cfg, model, params = decode_setup
    engine_qos = qos.QosEngine(serving_policy(), 1e-9, sample_fraction=1.0,
                               window=8)
    eng = ServingEngine(model, params, slots=2, max_len=32, prompt_len=8,
                        qos=engine_qos)
    for r in _requests(cfg, 2, gen=5):
        eng.submit(r)
    stats = eng.run_until_drained()
    ms = engine_qos.monitor.stats()
    assert stats.canary_ticks > 0 and ms.samples == stats.canary_ticks
    assert ms.mean_error == 0.0 and ms.estimate == 0.0
    assert stats.taf_skipped == 0


def test_serving_qos_requires_taf_decode(decode_setup):
    from repro.models import build
    from repro.serving import ServingEngine
    cfg, _, params = decode_setup
    plain = build(dataclasses.replace(cfg, approx_decode=ApproxSpec()))
    with pytest.raises(ValueError, match="decode-time TAF"):
        ServingEngine(plain, params, qos=qos.QosEngine(
            serving_policy(), 0.1))


def test_serving_qos_rejects_structurally_mismatched_ladder(decode_setup):
    """The online actuator writes only the threshold scalar, so a ladder
    calibrated under different TAF structural params (a different
    stability detector) must be rejected up front."""
    from repro.serving import ServingEngine
    cfg, model, params = decode_setup      # model runs (hSize=2, pSize=4)
    mismatched = qos.QosPolicy.from_records(
        [taf_record(0.1, 0.02, 1.5, h=5, p=9)], use_modeled=True)
    with pytest.raises(ValueError, match="structural"):
        ServingEngine(model, params, qos=qos.QosEngine(mismatched, 0.1))


def test_serving_latency_stats(decode_setup):
    from repro.serving import ServingEngine
    cfg, model, params = decode_setup
    eng = ServingEngine(model, params, slots=2, max_len=32, prompt_len=8)
    reqs = _requests(cfg, 4, gen=4)
    for r in reqs:
        eng.submit(r)
    stats = eng.run_until_drained()
    assert stats.finished == 4
    assert len(stats.ttft_s) == 4 and len(stats.latency_s) == 4
    lat = stats.latency_summary()
    assert lat["requests"] == 4
    assert lat["ttft_p99_s"] >= lat["ttft_p50_s"] >= 0.0
    assert lat["latency_p99_s"] >= lat["latency_p50_s"] >= 0.0
    # latency includes queueing: never below time-to-first-token
    assert all(l >= t for l, t in zip(sorted(stats.latency_s),
                                      sorted(stats.ttft_s)))
    fresh = ServingEngine(model, params, slots=2, max_len=32, prompt_len=8)
    assert fresh.stats.latency_summary()["ttft_p50_s"] is None


def _undonated(eng):
    """Give `eng` the serve step without cache donation: each tick then
    leaves its input cache alive, as the engine did before donating."""
    from repro.launch import steps
    eng._serve = jax.jit(steps.make_serve_step(eng.model))
    return eng


class _LogitsQos(qos.QosEngine):
    """A QoS engine whose serving loop scores every canary from both
    steps' logits, whatever the metric."""
    scores_tokens = False


@pytest.mark.parametrize("metric", ["mape", "mcr"])
def test_serving_donated_cache_keeps_tokens_and_canary_scores(decode_setup,
                                                              metric):
    """The QoS engine with a donated cache serves the same tokens and the
    same canary scores as without donation. On every canary tick the
    score equals the metric over the live lanes of the precise and the
    approximate step run again on a saved copy of the pre-tick cache and
    tokens. Under "mcr" the canary copies only the exact step's tokens to
    the host, and an engine made to score from both steps' logits gives
    the same scores, knob moves and tokens."""
    import jax.numpy as jnp
    from repro.launch import steps
    from repro.serving import ServingEngine
    cfg, model, params = decode_setup
    runs = {}
    names = ("donated", "undonated") + (("logits",) if metric == "mcr"
                                        else ())
    for name in names:
        engine_qos = (_LogitsQos if name == "logits" else qos.QosEngine)(
            serving_policy(metric), 2.0, sample_fraction=1.0, window=4,
            config=ctl_config(min_samples=1, hold_ticks=1))
        eng = ServingEngine(model, params, slots=2, max_len=32,
                            prompt_len=8, qos=engine_qos)
        if name == "undonated":
            _undonated(eng)
        saved, canaries = [], []
        serve, observe = eng._serve, engine_qos.observe_qoi

        def step(params, cache, tokens, pos, serve=serve, saved=saved):
            saved.append((jax.tree.map(jnp.copy, cache), jnp.copy(tokens),
                          pos))
            return serve(params, cache, tokens, pos)

        def scored(ex, ap, classes, shard=None, observe=observe,
                   saved=saved, canaries=canaries, eng=eng):
            live = [i for i, r in enumerate(eng.active) if r is not None]
            canaries.append((saved[-1], live, np.asarray(ex),
                             observe(ex, ap, classes, shard=shard)))
            return canaries[-1][3]
        eng._serve = step
        engine_qos.observe_qoi = scored
        reqs = _requests(cfg, 3, gen=10, cls="batch")
        for r in reqs:
            eng.submit(r)
        for _ in range(4):
            eng.tick()
        engine_qos.monitor.inject(10.0)       # a fault: the knob falls back
        stats = eng.run_until_drained()
        assert stats.finished == 3
        runs[name] = ([r.output for r in reqs], canaries, stats, eng)
    out_d, can_d, stats_d, eng = runs["donated"]
    assert stats_d.taf_skipped > 0
    assert len(can_d) == stats_d.canary_ticks == stats_d.ticks
    assert {m.reason for m in eng.knob_events} >= {"loosen", "fallback"}
    for out, can, stats, other in runs.values():
        assert out == out_d
        assert stats.taf_skipped == stats_d.taf_skipped
        assert [c[3] for c in can] == [c[3] for c in can_d]
        assert other.knob_log == eng.knob_log
        assert ([(p.index, p.event)
                 for p in other.qos.controllers["default"].trajectory]
                == [(p.index, p.event)
                    for p in eng.qos.controllers["default"].trajectory])
    plain = jax.jit(steps.make_serve_step(eng.model))
    metric_fn = {"mape": mape, "mcr": mcr}[metric]
    for (cache, tokens, pos), live, ex, err in can_d:
        exact_tokens, exact = eng._serve_exact(eng.params, cache, tokens, pos)
        _, approx, _ = plain(eng.params, cache, tokens, pos)
        exact, approx = np.asarray(exact)[live], np.asarray(approx)[live]
        if metric == "mcr":
            np.testing.assert_array_equal(ex, np.asarray(exact_tokens)[live])
            exact, approx = np.argmax(exact, -1), np.argmax(approx, -1)
        else:
            np.testing.assert_array_equal(ex, exact)
        assert err == metric_fn(exact, approx)
    logits_bytes = 2 * 2 * cfg.padded_vocab_size * 4
    if metric == "mcr":
        assert any(c[3] > 0 for c in can_d)
        assert stats_d.canary_host_bytes == 4 * 2 * stats_d.canary_ticks
        stats_l = runs["logits"][2]
        assert stats_l.canary_host_bytes == (logits_bytes
                                             * stats_l.canary_ticks)
    else:
        assert stats_d.canary_host_bytes == (logits_bytes
                                             * stats_d.canary_ticks)


@pytest.mark.parametrize("engine", ["precise", "qos"])
def test_warmup_then_ticks_consume_the_pre_tick_cache(decode_setup, engine):
    """`warmup()` and the ticks after it never read a donated buffer: each
    tick consumes the cache it starts from, and admission into a live
    cache splices into the cache the serve step returned."""
    from repro.models import build
    from repro.serving import ServingEngine
    cfg, model, params = decode_setup
    kw = {}
    if engine == "precise":
        model = build(dataclasses.replace(cfg, approx_decode=ApproxSpec()))
    else:
        kw["qos"] = qos.QosEngine(serving_policy(), 2.0, sample_fraction=1.0,
                                  window=4)
    eng = ServingEngine(model, params, slots=2, max_len=32, prompt_len=8,
                        **kw)
    eng.warmup()
    first, second = _requests(cfg, 2, gen=6)
    eng.submit(first)
    eng.tick()                      # the first admission prefills the batch
    eng.submit(second)
    eng.tick()                      # spliced into the live cache
    for _ in range(3):
        pre = eng.cache
        eng.tick()
        kv = jax.tree.leaves(pre["dense"])
        assert kv and all(leaf.is_deleted() for leaf in kv)
        assert not any(leaf.is_deleted()
                       for leaf in jax.tree.leaves(eng.cache))
    stats = eng.run_until_drained()
    assert stats.finished == 2
    assert len(first.output) == len(second.output) == 6
    assert eng._serve._cache_size() == 1


# -------------------------------------------------------------- calibration

def test_decode_calibration_sweeps_through_harness(decode_setup, tmp_path):
    cfg, _, _ = decode_setup
    app = qos.make_decode_app(cfg, gen=4, batch=1)
    db = str(tmp_path / "db.json")
    grid = qos.threshold_grid(cfg, [0.02, 0.3])
    recs = sweep(app, grid, repeats=1, db_path=db)
    assert len(recs) == 2
    assert all(np.isfinite(r.error) for r in recs)
    assert recs[1].approx_fraction >= recs[0].approx_fraction
    # threshold 0.0 (precise) reproduces the exact baseline bit for bit
    exact = app.exact()
    again = app.run(ApproxSpec())
    np.testing.assert_array_equal(exact.qoi, again.qoi)
    assert exact.approx_fraction == 0.0
    # structural mismatch fails fast
    bad = ApproxSpec(Technique.TAF, Level.BLOCK, taf=TAFParams(5, 9, 0.1))
    with pytest.raises(ValueError, match="structural"):
        app.run(bad)
    # the sweep DB feeds the policy loader
    pol = qos.QosPolicy.from_db(db, app="taf_decode", use_modeled=True)
    assert pol.entries[0].precise
