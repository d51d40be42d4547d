"""approxlint test suite (docs/analysis.md): the findings/allowlist
plumbing, each rule against KNOWN-BAD fixtures (a baked constant and a
static argument for A001, taint into control flow and gather indices for
A003, dominated/stale/duplicated ladders for A004, uncommitted serve-step
leaves for A005), the two opt-in lint hooks, the CLI's exit-code
contract, and the meta-test that the current tree itself lints clean."""
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from repro.analysis.findings import (AllowEntry, Allowlist, Finding, Report,
                                     Severity, default_allowlist_path)
from repro.analysis.taint import find_taint_sinks
from repro.analysis.trace import jaxpr_fingerprint, probe_knob
from repro.analysis import rules as rules_mod
from repro.analysis.lint import run_lint


# ------------------------------------------------------------- findings

def _f(rule="A001", sev=Severity.ERROR, subject="kernels.toy.knob"):
    return Finding(rule, sev, subject, "msg", {})


def test_severity_parse_and_order():
    assert Severity.parse("warning") is Severity.WARNING
    assert Severity.INFO < Severity.WARNING < Severity.ERROR
    with pytest.raises(ValueError):
        Severity.parse("fatal")


def test_allowlist_matches_by_rule_and_fnmatch():
    allow = Allowlist([AllowEntry("A001", "kernels.*", reason="r")])
    assert allow.match(_f()) is not None
    assert allow.match(_f(rule="A002")) is None          # rule must match
    assert allow.match(_f(subject="regions.toy")) is None


def test_allowlist_load_rejects_empty_reason(tmp_path):
    p = tmp_path / ".approxlint.json"
    p.write_text(json.dumps(
        {"version": 1,
         "allow": [{"rule": "A001", "subject": "x", "reason": ""}]}))
    with pytest.raises(ValueError, match="reason"):
        Allowlist.load(str(p))


def test_default_allowlist_path_walks_up(tmp_path):
    (tmp_path / ".approxlint.json").write_text('{"version":1,"allow":[]}')
    nested = tmp_path / "a" / "b"
    nested.mkdir(parents=True)
    assert default_allowlist_path(str(nested)) == str(
        tmp_path / ".approxlint.json")


def test_report_routes_allowlisted_and_fails_on_rule_crash():
    rep = Report()
    allow = Allowlist([AllowEntry("A001", "kernels.*", reason="known")])
    rep.extend([_f(), _f(rule="A002", subject="bench.x")], allow)
    assert [f.rule for f in rep.findings] == ["A002"]
    assert len(rep.allowlisted) == 1
    assert rep.failed(Severity.ERROR)
    clean = Report()
    assert not clean.failed()
    clean.errors.append("A003: crashed")
    assert clean.failed()         # a crashed rule always fails the lint


# ---------------------------------------------------- A001: knob tracing

def test_probe_knob_traced_clean():
    x = jnp.arange(8.0)
    res = probe_knob(lambda th: jnp.where(jnp.abs(x) < th, 0.0, x))
    assert res.verdict == "traced" and res.clean


def test_probe_knob_static_argument_is_a_finding():
    x = jnp.arange(8.0)
    f = jax.jit(lambda x, th: jnp.where(jnp.abs(x) < th, 0.0, x),
                static_argnames=("th",))
    res = probe_knob(lambda th: f(x, th))
    assert res.verdict == "static"
    assert res.error


def test_probe_knob_python_control_flow_is_a_finding():
    x = jnp.arange(8.0)

    def branchy(th):
        return x * 2 if th > 0.5 else x      # concretizes the tracer
    res = probe_knob(branchy)
    assert res.verdict == "static"


def test_probe_knob_baked_constant_is_a_finding():
    x = jnp.arange(8.0)

    def build(v):          # captures the VALUE before the trace: baked
        return lambda th: jnp.where(jnp.abs(x) < float(v), 0.0, x) + th * 0
    fingerprints = [
        jaxpr_fingerprint(jax.make_jaxpr(build(v))(jnp.float32(v)))
        for v in (0.25, 0.75)]
    assert fingerprints[0] != fingerprints[1]

    holder = {"v": 0.0}

    def leaky(th):           # ignores th; bakes the swept value instead
        holder["v"] += 0.5
        return jnp.where(jnp.abs(x) < holder["v"], 0.0, x)
    assert probe_knob(leaky).verdict == "baked"


def test_fingerprint_normalizes_hex_addresses():
    a = "custom_call[callback=<function f at 0x7f01>]"
    b = "custom_call[callback=<function f at 0x7f02>]"
    import re
    from repro.analysis.trace import _HEX_ADDR
    assert _HEX_ADDR.sub("0x", a) == _HEX_ADDR.sub("0x", b)


def test_check_spec_grouping_clean_and_leaky(monkeypatch):
    from repro.core import batching
    from repro.core.harness import taf_grid
    from repro.core.types import Level
    grid = taf_grid(h_sizes=(3,), p_sizes=(2,), thresholds=(0.02, 0.1),
                    levels=(Level.BLOCK,))
    assert rules_mod.check_spec_grouping(grid) == []

    orig = batching.static_key

    def leaky(spec):         # the knob value leaks into the static key
        k = orig(spec)
        return k + (spec.taf.rsd_threshold,) if k and spec.taf else k
    monkeypatch.setattr(batching, "static_key", leaky)
    findings = rules_mod.check_spec_grouping(grid, subject_prefix="t")
    assert [f.rule for f in findings] == ["A001"]
    assert "static_key" in findings[0].subject


# -------------------------------------------------------- A003: taint

def test_taint_cond_predicate_sink():
    def step(memo, x):
        return jax.lax.cond(jnp.sum(memo) > 0.0,
                            lambda v: v * 2.0, lambda v: v, x)
    closed = jax.make_jaxpr(step)(jnp.ones(4), jnp.ones(4))
    sinks = find_taint_sinks(closed, tainted_inputs=[0])
    assert any(s.kind == "branch predicate" for s in sinks)
    assert find_taint_sinks(closed, tainted_inputs=[1]) == []


def test_taint_gather_indices_sink():
    def step(memo, x):
        idx = jnp.argmax(memo).astype(jnp.int32)
        return x[idx]
    closed = jax.make_jaxpr(step)(jnp.ones(4), jnp.ones(4))
    sinks = find_taint_sinks(closed, tainted_inputs=[0])
    assert any("indices" in s.kind for s in sinks)


def test_taint_while_predicate_via_carry_fixpoint():
    def step(memo, x):
        def cond(c):
            i, acc = c
            return acc < 10.0          # acc is memo-derived
        def body(c):
            i, acc = c
            return i + 1, acc + 1.0
        return jax.lax.while_loop(cond, body, (0, jnp.sum(memo)))
    closed = jax.make_jaxpr(step)(jnp.ones(4), jnp.ones(4))
    sinks = find_taint_sinks(closed, tainted_inputs=[0])
    assert any(s.kind == "while predicate" for s in sinks)


def test_taint_pure_arithmetic_is_clean():
    def step(memo, x):
        return x * jnp.tanh(memo) + jnp.sum(memo)
    closed = jax.make_jaxpr(step)(jnp.ones(4), jnp.ones(4))
    assert find_taint_sinks(closed, tainted_inputs=[0]) == []


def test_taint_walks_into_pjit():
    inner = jax.jit(lambda m, v: jax.lax.cond(
        m[0] > 0, lambda y: y, lambda y: -y, v))

    def step(memo, x):
        return inner(memo, x)
    closed = jax.make_jaxpr(step)(jnp.ones(4), jnp.ones(4))
    sinks = find_taint_sinks(closed, tainted_inputs=[0])
    assert any(s.kind == "branch predicate" for s in sinks)
    assert all("jit" in s.path for s in sinks)


# ------------------------------------------------------ A004: ladders

def _rung(thresh, error, speedup, h=2, p=4, **over):
    from repro.core.harness import spec_hash
    spec = {"technique": "taf", "level": "block", "hSize": h, "pSize": p,
            "thresh": thresh}
    d = {"spec": spec, "error": error, "speedup": speedup,
         "modeled_speedup": speedup, "spec_hash": spec_hash(spec)}
    d.update(over)
    return d


def _precise_rung():
    from repro.core.harness import spec_hash
    spec = {"technique": "none"}
    return {"spec": spec, "error": 0.0, "speedup": 1.0,
            "modeled_speedup": 1.0, "spec_hash": spec_hash(spec)}


def _doc(entries, **over):
    d = {"version": 1, "app": "toy", "metric": "mape",
         "use_modeled": False, "entries": entries}
    d.update(over)
    return d


def _a004(doc, **kw):
    return rules_mod.check_policy_document(doc, subject="p", **kw)


def test_a004_clean_ladder():
    doc = _doc([_precise_rung(), _rung(0.05, 0.01, 1.5),
                _rung(0.2, 0.04, 2.2)])
    assert _a004(doc) == []


def test_a004_dominated_rung():
    doc = _doc([_precise_rung(), _rung(0.05, 0.01, 2.0),
                _rung(0.2, 0.04, 1.8)])    # more error, LESS speedup
    msgs = [f.message for f in _a004(doc)]
    assert any("dominated" in m for m in msgs)


def test_a004_non_ascending_error():
    doc = _doc([_precise_rung(), _rung(0.05, 0.04, 1.5),
                _rung(0.2, 0.04, 2.2)])    # equal error on a later rung
    msgs = [f.message for f in _a004(doc)]
    assert any("ascending" in m for m in msgs)


def test_a004_missing_precise_anchor():
    doc = _doc([_rung(0.05, 0.01, 1.5)])
    assert any("#rung0" in f.subject for f in _a004(doc))


def test_a004_sub_1x_rung_and_duplicate_spec():
    doc = _doc([_precise_rung(), _rung(0.05, 0.01, 0.9)])
    assert any("<= 1x" in f.message for f in _a004(doc))
    doc = _doc([_precise_rung(), _rung(0.05, 0.01, 1.5),
                _rung(0.05, 0.04, 2.0)])   # same spec dict twice
    assert any("duplicate spec" in f.message for f in _a004(doc))


def test_a004_stale_spec_hash():
    bad = _rung(0.05, 0.01, 1.5)
    bad["spec_hash"] = "deadbeef"
    msgs = [f.message for f in _a004(_doc([_precise_rung(), bad]))]
    assert any("spec_hash" in m for m in msgs)


def test_a004_model_taf_mismatch_and_structural_split():
    doc = _doc([_precise_rung(), _rung(0.05, 0.01, 1.5, h=2, p=4)])
    assert _a004(doc, model_taf=(2, 4)) == []
    assert any("target model" in f.message
               for f in _a004(doc, model_taf=(8, 2)))
    split = _doc([_precise_rung(), _rung(0.05, 0.01, 1.5, h=2, p=4),
                  _rung(0.2, 0.04, 2.2, h=8, p=2)])
    assert any("structural" in f.message for f in _a004(split))


def test_a004_raw_json_not_healed_load(tmp_path):
    """QosPolicy.load re-normalizes, so the linter must see the RAW file:
    a saved ladder with a dominated rung loads 'clean' but lints dirty."""
    from repro import qos
    doc = _doc([_precise_rung(), _rung(0.05, 0.01, 2.0),
                _rung(0.2, 0.04, 1.8)])
    p = tmp_path / "policy.json"
    p.write_text(json.dumps(doc))
    healed = qos.QosPolicy.load(str(p))
    assert len(healed.entries) == 2        # load silently drops the rung
    findings = rules_mod.check_policy_file(str(p))
    assert any(f.rule == "A004" for f in findings)


def test_a004_saved_policy_roundtrip_is_clean(tmp_path):
    from repro import qos
    from repro.core.harness import Record
    recs = [Record(app="toy",
                   spec={"technique": "taf", "level": "block", "hSize": 2,
                         "pSize": 4, "thresh": t},
                   error=e, speedup=s, modeled_speedup=s,
                   approx_fraction=0.5, wall_time_s=1.0, exact_time_s=1.0,
                   extra={})
            for t, e, s in ((0.05, 0.002, 1.2), (0.1, 0.01, 1.5),
                            (0.2, 0.04, 2.2))]
    pol = qos.QosPolicy.from_records(recs)
    p = tmp_path / "ok.json"
    pol.save(str(p))
    assert rules_mod.check_policy_file(str(p)) == []


def test_a004_unreadable_file_reported():
    findings = rules_mod.check_policy_file("/nonexistent/policy.json")
    assert [f.rule for f in findings] == ["A004"]
    assert "unreadable" in findings[0].message


# --------------------------------- A006: statically-hopeless rungs

def _iact_rung(tsize, thresh, error, speedup):
    from repro.core.harness import spec_hash
    spec = {"technique": "iact", "level": "block", "tSize": tsize,
            "thresh": thresh, "tPerBlock": 1}
    return {"spec": spec, "error": error, "speedup": speedup,
            "modeled_speedup": speedup, "spec_hash": spec_hash(spec)}


def test_a006_oversized_iact_table_flagged():
    """An iACT rung whose table probes out-cost the memoized region: the
    measured ladder may look fine (A004-clean), but the predicted speedup
    on the target machine is sub-1x -- a rung that should never ship."""
    doc = _doc([_precise_rung(), _iact_rung(4096, 0.2, 0.01, 1.5)])
    findings = rules_mod.check_policy_cost(doc, subject="p")
    assert [f.rule for f in findings] == ["A006"]
    assert findings[0].subject == "p#rung1"
    assert findings[0].severity is rules_mod.Severity.ERROR
    assert findings[0].detail["predicted_speedup"] <= 1.0


def test_a006_plausible_ladder_clean():
    doc = _doc([_precise_rung(), _rung(0.5, 0.01, 1.2),
                _iact_rung(2, 0.2, 0.04, 1.1)])
    assert rules_mod.check_policy_cost(doc, subject="p") == []


def test_a006_unparseable_spec_left_to_a004():
    doc = _doc([_precise_rung(),
                {"spec": {"technique": "taf", "hSize": -1},
                 "error": 0.01, "speedup": 1.5}])
    assert rules_mod.check_policy_cost(doc, subject="p") == []


def test_a006_policy_file_roundtrip(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        _doc([_precise_rung(), _iact_rung(4096, 0.2, 0.01, 1.5)])))
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_doc([_precise_rung(),
                                     _rung(0.5, 0.01, 1.2)])))
    findings = rules_mod.rule_a006([str(bad), str(good)])
    assert [f.rule for f in findings] == ["A006"]
    assert str(bad) in findings[0].subject


# --------------------------------- A007: divergent loop carries

def _while_program(body_update):
    """A while loop with a data-dependent trip count whose carry folds in
    the tainted memo value via `body_update(v, memo_scalar)`."""
    def fn(state, x):
        def cond(c):
            _, v = c
            return v < 1e6
        def body(c):
            i, v = c
            return i + 1, body_update(v, state["memo"][0])
        return jax.lax.while_loop(cond, body, (jnp.int32(0), x))
    args = ({"memo": jnp.ones((4,), jnp.float32)}, jnp.float32(1.0))
    return fn, args


def test_a007_amplifying_while_carry_flagged():
    # v <- 2v + memo: the carry's relative error grows every iteration
    # and the trip count is data-dependent -- no static bound exists
    fn, args = _while_program(lambda v, m: 2.0 * v + m)
    findings = rules_mod.check_divergence(fn, args, ("memo",), "toy.loop")
    assert [f.rule for f in findings] == ["A007"]
    assert findings[0].severity is rules_mod.Severity.ERROR
    assert findings[0].detail["loop"]["kind"] == "while"
    assert findings[0].detail["loop"]["gain"] > 1.0


def test_a007_bounded_while_carry_clean():
    # v <- max(v, memo): the carry error saturates at the injected bound
    # (max is error-preserving), so the fixpoint converges -- no finding
    fn, args = _while_program(jnp.maximum)
    assert rules_mod.check_divergence(fn, args, ("memo",), "toy.loop") == []


def test_a007_no_tainted_leaves_is_a_warning():
    fn, args = _while_program(lambda v, m: 2.0 * v + m)
    findings = rules_mod.check_divergence(fn, args, ("nonexistent",), "toy")
    assert [f.rule for f in findings] == ["A007"]
    assert findings[0].severity is rules_mod.Severity.WARNING
    assert "unchecked" in findings[0].message


def test_a007_committed_region_steps_clean():
    """The shipped region step programs must not amplify their memoized
    values unboundedly -- the same contract the tree-wide lint enforces."""
    assert rules_mod.rule_a007(("regions",)) == []


# ------------------------------------------- A005 + the two lint hooks

@pytest.fixture(scope="module")
def engine():
    from repro.analysis.targets import engine_fixture
    return engine_fixture()


def test_a005_committed_engine_is_clean(engine):
    assert rules_mod.check_engine_placement(engine) == []


def test_a005_uncommitted_leaves_flagged(engine):
    from repro.analysis.targets import decode_fixture
    from repro.serving.scheduler import ServingEngine
    fx = decode_fixture()
    eng = ServingEngine(fx["model"], fx["params"], slots=2, max_len=16,
                        prompt_len=4, devices=1)
    eng.params = fx["params"]          # raw host arrays: no mesh commitment
    findings = rules_mod.check_engine_placement(eng)
    assert [f.key for f in findings] == ["A005:serving.engine.params"]
    assert "without mesh commitment" in findings[0].message


def test_engine_lint_hook_clean_and_raises():
    from repro.analysis.targets import decode_fixture
    from repro.serving.scheduler import ServingEngine
    fx = decode_fixture()
    ServingEngine(fx["model"], fx["params"], slots=2, max_len=16,
                  prompt_len=4, devices=1, lint=True)   # must not raise
    orig = jax.device_put
    try:
        jax.device_put = lambda tree, *a, **k: tree   # sabotage placement
        with pytest.raises(ValueError, match="A005"):
            ServingEngine(fx["model"], fx["params"], slots=2, max_len=16,
                          prompt_len=4, devices=1, lint=True)
    finally:
        jax.device_put = orig


def test_run_specs_lint_hook(monkeypatch):
    sys.path.insert(0, "examples")
    from apps import approx_ffn
    from repro.core import batching
    from repro.core.harness import run_specs, taf_grid
    from repro.core.types import Level
    grid = taf_grid(h_sizes=(3,), p_sizes=(2,), thresholds=(0.02, 0.1),
                    levels=(Level.BLOCK,))
    app = approx_ffn.make_app(substrate="host")
    assert len(run_specs(app, grid, repeats=1, lint=True)) == len(grid)

    orig = batching.static_key

    def leaky(spec):
        k = orig(spec)
        return k + (spec.taf.rsd_threshold,) if k and spec.taf else k
    monkeypatch.setattr(batching, "static_key", leaky)
    with pytest.raises(ValueError, match="A001"):
        run_specs(app, grid, repeats=1, lint=True)


# ------------------------------------------------- CLI + the meta-test

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cli(*argv):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", *argv],
        capture_output=True, text=True, env=env, cwd=_ROOT)


def test_cli_bad_policy_exits_1_good_policy_0(tmp_path):
    bad = _doc([_precise_rung(), _rung(0.05, 0.01, 2.0),
                _rung(0.2, 0.04, 1.8)])
    bp = tmp_path / "bad.json"
    bp.write_text(json.dumps(bad))
    r = _cli("--rules", "A004", "--policies", str(bp), "--format", "json")
    assert r.returncode == 1, r.stderr
    doc = json.loads(r.stdout)
    assert doc["summary"]["errors"] >= 1
    assert all(f["rule"] == "A004" for f in doc["findings"])

    good = _doc([_precise_rung(), _rung(0.05, 0.01, 1.5)])
    gp = tmp_path / "good.json"
    gp.write_text(json.dumps(good))
    r = _cli("--rules", "A004", "--policies", str(gp))
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_allowlist_is_load_bearing():
    """The committed allowlist is what keeps the structural-perforation
    probes green: --no-allowlist must fail on exactly those A001s."""
    r = _cli("--apps", "kernels", "--rules", "A001", "--no-allowlist",
             "--format", "json")
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    subjects = {f["subject"] for f in doc["findings"]}
    assert subjects == {"kernels.perforated_matmul.perfo",
                        "kernels.perforated_attention.perfo"}
    r = _cli("--apps", "kernels", "--rules", "A001")
    assert r.returncode == 0, r.stdout + r.stderr


def test_meta_current_tree_lints_clean():
    """The tree itself must lint clean under the committed allowlist --
    the same contract CI's lint step enforces. Serving group excluded
    here (the engine fixture executes; it has its own tests above)."""
    allow = Allowlist.load(default_allowlist_path(_ROOT))
    rep = run_lint(apps=("kernels", "regions", "ffn"), allowlist=allow)
    assert not rep.errors, rep.errors
    assert not rep.findings, rep.render_text()
    assert len(rep.allowlisted) == 3     # pinned: bump with .approxlint.json
