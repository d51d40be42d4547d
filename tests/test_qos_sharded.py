"""Sharded QoS serving regression suite (ISSUE 6).

Three invariants pinned under the 8-fake-device harness (subprocess, as in
``test_distributed.py``: the device count must be set before jax
initializes):

- **bit-parity**: the same engine config (8 logical shards) produces
  bit-identical decode outputs and canary error estimates on an 8-device
  and a 1-device mesh -- per-shard compute has no cross-shard collectives,
  so the device count must never change numerics;
- **zero recompiles**: per-shard TAF knob moves are traced-data writes
  into the cache pytree; the jitted sharded serve step's compile cache
  must not grow across them (``_cache_size()``, as in
  ``test_kernel_substrate.py``);
- **deterministic per-shard fallback**: the fault drill
  (``QosEngine.inject(error, shard=s)``) produces the same controller
  trajectories run-to-run and backs off only the drilled shard's classes.

Host-level tests (no subprocess) cover the per-shard control-plane
arithmetic: strictest-live-rung reduction, exposure attribution, and the
sharding-mode guard rails.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


# shared preamble: a smoke model + a 3-rung synthetic ladder, and a driver
# that serves a seeded trace on a (devices, shards) engine and returns the
# artifacts the tests compare
_PREAMBLE = r"""
import numpy as np, jax
from repro import qos
from repro.models import build
from repro.serving import Request, ServingEngine

cfg = qos.default_decode_cfg()
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
records = [
    {"app": "taf_decode", "spec": {"technique": "taf", "level": "block",
     "hSize": 2, "pSize": 4, "thresh": th}, "error": e, "speedup": s,
     "modeled_speedup": s, "workload": {}}
    for th, e, s in [(0.02, 0.005, 1.2), (0.06, 0.02, 1.5),
                     (0.3, 0.08, 2.0)]]
policy = qos.QosPolicy.from_records(records, metric="mcr")

def run(devices, shards, slots, *, seed=0, inject_at=None, inject_shard=None,
        engine=qos.QosEngine):
    engine_qos = engine(policy, {"default": 0.10, "batch": 0.5},
                        sample_fraction=0.5, window=8)
    eng = ServingEngine(model, params, slots=slots, max_len=48,
                        prompt_len=8, qos=engine_qos, devices=devices,
                        shards=shards)
    eng.warmup()
    rng = np.random.RandomState(seed)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab_size, 8),
                    max_new_tokens=6,
                    qos_class="default" if i % 2 == 0 else "batch")
            for i in range(slots * 2)]
    for r in reqs:
        eng.submit(r)
    for tick in range(200):
        if inject_at is not None and tick == inject_at:
            eng.qos.inject(10.0, shard=inject_shard)
        if eng.tick() == 0 and not eng.queue:
            break
    return eng, reqs
"""


class TestShardedParity:
    def test_device_count_invariance(self):
        """8 logical shards on an 8-device mesh vs the SAME 8 shards on a
        1-device mesh: decode outputs (per request, token for token),
        canary error estimates, and knob logs are bit-identical."""
        out = run_sub(_PREAMBLE + r"""
e8, r8 = run(8, 8, 8)
e1, r1 = run(1, 8, 8)
assert [r.output for r in r8] == [r.output for r in r1], "decode outputs"
s8, s1 = e8.qos.summary(), e1.qos.summary()
assert s8["estimate"] == s1["estimate"], (s8["estimate"], s1["estimate"])
assert s8["genuine_mean_error"] == s1["genuine_mean_error"]
assert e8.knob_log == e1.knob_log
assert e8.mesh_shape == (8, 1) and e1.mesh_shape == (1, 1)
assert e8.stats.tokens_out == e1.stats.tokens_out > 0
print("PARITY_OK", e8.stats.tokens_out)
""")
        assert "PARITY_OK" in out

    def test_sharded_vs_unsharded_outputs(self):
        """The sharded wrapper itself must not change numerics: one shard
        on a 1-device mesh reproduces the plain (unsharded) engine's
        outputs token for token."""
        out = run_sub(_PREAMBLE + r"""
es, rs = run(1, 1, 4)
ep, rp = run(None, None, 4)
assert ep.mesh_shape is None and es.mesh_shape == (1, 1)
assert [r.output for r in rs] == [r.output for r in rp], "decode outputs"
assert es.knob_log == ep.knob_log or (
    # unsharded knob entries are scalars, sharded are 1-tuples
    [(t, (v,) if not isinstance(v, tuple) else v) for t, v in ep.knob_log]
    == es.knob_log)
print("WRAP_OK")
""")
        assert "WRAP_OK" in out


class TestShardedTokenCanary:
    def test_token_canaries_score_as_the_logits_path(self):
        """Under "mcr" each shard's canary is scored from the two steps'
        token ids: on the same seeded trace, with a drilled shard, every
        shard's exposure errors, the class evidence, the knob log and the
        tokens equal those of an engine made to score from both steps'
        logits, and only the exact step's tokens reach the host."""
        out = run_sub(_PREAMBLE + r"""
class LogitsQos(qos.QosEngine):
    scores_tokens = False

et, rt = run(2, 2, 4, inject_at=3, inject_shard=1)
el, rl = run(2, 2, 4, inject_at=3, inject_shard=1, engine=LogitsQos)
assert [r.output for r in rt] == [r.output for r in rl], "decode outputs"
exp_t, exp_l = et.qos._shard_exposure, el.qos._shard_exposure
assert exp_t == exp_l, (exp_t, exp_l)
assert exp_t[0] and exp_t[1]
assert any(e > 0 for errs in exp_t.values() for e in errs)
for cls, mon in et.qos.class_monitors.items():
    assert list(mon._window) == list(el.qos.class_monitors[cls]._window)
assert et.knob_log == el.knob_log and len(et.knob_log) > 1
n = et.stats.canary_ticks
assert n == el.stats.canary_ticks > 0
assert et.stats.canary_host_bytes == 4 * 4 * n
assert el.stats.canary_host_bytes == 2 * 4 * cfg.padded_vocab_size * 4 * n
print("TOKENS_OK", n, sum(map(sum, exp_t.values())))
""", n_devices=2)
        assert "TOKENS_OK" in out


class TestZeroRecompile:
    def test_per_shard_knob_moves_do_not_recompile(self):
        """The per-shard threshold vector is traced DATA: serving under a
        changing knob vector must not grow the serve step's compile
        cache, and the written thresholds must be live in the cache."""
        out = run_sub(_PREAMBLE + r"""
import jax.numpy as jnp
from repro.qos import set_decode_threshold
eng, reqs = run(8, 8, 8)   # compiles every signature serving hits
base = eng._serve._cache_size()
pos = jnp.int32(10)
vectors = [(0.3,) * 8,
           (0.0, 0.3) * 4,
           tuple(0.1 * s for s in range(8)),
           (0.0,) * 8]
for vec in vectors:
    eng.cache = eng._place_cache(set_decode_threshold(eng.cache, vec))
    eng.tokens, _, eng.cache = eng._serve(eng.params, eng.cache,
                                          eng.tokens, pos)
    th = np.asarray(eng.cache["taf"]["threshold"])
    np.testing.assert_allclose(th[:, 0], np.asarray(vec), rtol=1e-6)
assert eng._serve._cache_size() == base, (
    f"serve step recompiled: {eng._serve._cache_size()} vs {base}")
print("NORECOMPILE_OK", base)
""")
        assert "NORECOMPILE_OK" in out


class TestPerShardFallback:
    def test_fault_drill_deterministic_and_localized(self):
        """Injecting a spike into ONE shard's canary stream (a) backs off
        the classes live on that shard, (b) leaves the engine-wide
        estimate fault-free (inject is not a genuine canary), and (c) is
        deterministic run to run."""
        out = run_sub(_PREAMBLE + r"""
runs = []
for _ in range(2):
    eng, _ = run(8, 8, 16, inject_at=4, inject_shard=7)
    s = eng.qos.summary()
    traj = {cls: [(p.step, p.index, p.event)
                  for p in ctl.trajectory]
            for cls, ctl in eng.qos.controllers.items()}
    runs.append((eng.knob_log, traj, s["injected_faults"],
                 s["fallback_rate"]))
assert runs[0] == runs[1], "fault drill is nondeterministic"
knob_log, traj, faults, fb = runs[0]
assert faults >= 1
assert fb > 0.0, "drill never forced a fallback tick"
events = [e for t in traj.values() for (_, _, e) in t]
assert any(e == "fallback" for e in events), events
print("DRILL_OK", faults, sorted(set(events)))
""")
        assert "DRILL_OK" in out


# ---------------------------------------------------------------------------
# host-level control-plane arithmetic (fast: no subprocess, no mesh)
# ---------------------------------------------------------------------------

def _mk_engine(n_shards=4, targets=None):
    from repro import qos
    records = [
        {"app": "taf_decode", "spec": {"technique": "taf", "level": "block",
         "hSize": 2, "pSize": 4, "thresh": th}, "error": e, "speedup": s,
         "modeled_speedup": s, "workload": {}}
        for th, e, s in [(0.02, 0.005, 1.2), (0.06, 0.02, 1.5),
                         (0.3, 0.08, 2.0)]]
    policy = qos.QosPolicy.from_records(records, metric="mcr")
    eng = qos.QosEngine(policy, targets or {"default": 0.10, "batch": 0.5},
                        sample_fraction=1.0, window=8)
    if n_shards:
        eng.enable_sharding(n_shards)
    return eng


class TestShardPlanReduction:
    def test_strictest_live_rung_per_shard_and_global(self):
        eng = _mk_engine(4)
        # put the two class controllers on different rungs
        eng.controller("default").index = 1
        eng.controller("batch").index = 3
        plan = eng.plan_shards([["default"], ["batch"],
                                ["default", "batch"], []])
        assert plan.sharded and plan.shard_indices == (1, 3, 1, 1)
        # global = strictest across shards WITH live lanes (empty shard 3
        # follows the default controller, it must not loosen the plan)
        assert plan.index == 1
        assert len(plan.shard_knobs) == 4

    def test_empty_shards_follow_default(self):
        eng = _mk_engine(2)
        eng.controller("default").index = 2
        plan = eng.plan_shards([[], []])
        assert plan.shard_indices == (2, 2)
        assert plan.index == 2

    def test_plan_validates_shard_count(self):
        eng = _mk_engine(4)
        with pytest.raises(ValueError):
            eng.plan_shards([["default"]])

    def test_enable_sharding_idempotent_but_not_resizable(self):
        eng = _mk_engine(4)
        eng.enable_sharding(4)          # idempotent
        with pytest.raises(ValueError):
            eng.enable_sharding(8)


class TestShardExposure:
    def test_exposure_attributed_to_shard_and_class(self):
        eng = _mk_engine(2)
        eng.plan_shards([["default"], ["batch"]])
        same = np.zeros((1, 4), np.float32)
        diff = np.zeros((1, 4), np.float32)
        diff[:, 1] = 1.0                 # argmax flips: mcr error = 1
        eng.observe_shard(0, same, same, ["default"])
        eng.observe_shard(1, same, diff, ["batch"])
        exp = eng.summary()["shard_exposure"]
        assert exp[0]["exposed_mean_error"] == 0.0
        assert exp[1]["exposed_mean_error"] == 1.0
        s = eng.summary()
        assert s["classes"]["default"]["exposed_mean_error"] == 0.0
        assert s["classes"]["batch"]["exposed_mean_error"] == 1.0

    def test_shard_inject_hits_only_that_shards_classes(self):
        eng = _mk_engine(2)
        eng.plan_shards([["default"], ["batch"]])
        eng.inject(5.0, shard=1)
        assert eng.monitor.injected == 1
        assert eng.class_monitors["batch"].injected == 1
        assert eng.class_monitors["default"].injected == 0
