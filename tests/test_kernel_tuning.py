"""Block-shape autotuner (`kernels/tuning.py`): search-space validity,
pipelined-variant bit parity, cache determinism, compile-count guarantees,
and the A002 tuning-cache audit."""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.types import PerforationKind, PerforationParams
from repro.kernels import ops, tuning


@pytest.fixture(autouse=True)
def _isolated_cache():
    """No test may read or write the committed tuning cache: pin an empty
    in-memory cache as the ambient default and restore lazy-loading after."""
    tuning.set_default_cache(tuning.TuningCache())
    yield
    tuning.set_default_cache(None)


def _arrays(kernel, seed=0):
    rng = np.random.RandomState(seed)

    def f32(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    if kernel == "taf_matmul":
        return (f32(128, 32), f32(32, 32))
    if kernel == "iact_rowfn":
        return (f32(128, 32), f32(32, 64), f32(64, 32))
    if kernel == "perforated_matmul":
        return (f32(64, 64), f32(64, 64))
    if kernel == "perforated_attention":
        q = f32(1, 2, 128, 16)
        return (q, q, q)
    raise ValueError(kernel)


class TestSearchSpace:
    @pytest.mark.parametrize("kernel", tuning.KERNELS)
    def test_all_candidates_divisor_valid(self, kernel):
        shapes = tuning.operand_shapes(_arrays(kernel))
        space = tuning.search_space(kernel, shapes)
        assert space
        for cfg in space:
            assert tuning.validate_config(kernel, shapes, cfg) is None
        # deterministic enumeration (the pre-prune tie-break relies on it)
        assert space == tuning.search_space(kernel, shapes)

    def test_rejects_non_divisors_and_unknowns(self):
        shapes = ((128, 32), (32, 32))
        assert "does not divide" in tuning.validate_config(
            "taf_matmul", shapes, {"block_m": 48, "block_n": 32})
        assert "missing" in tuning.validate_config(
            "taf_matmul", shapes, {"block_m": 32})
        assert "unknown to" in tuning.validate_config(
            "taf_matmul", shapes,
            {"block_m": 32, "block_n": 32, "block_k": 32})
        assert "unknown kernel" in tuning.validate_config(
            "nope", shapes, {})

    def test_vmem_budget_bounds_the_space(self):
        shapes = tuning.operand_shapes(_arrays("perforated_matmul"))
        for cfg in tuning.search_space("perforated_matmul", shapes):
            assert tuning.vmem_bytes("perforated_matmul", shapes,
                                     cfg) <= tuning.VMEM_BUDGET_BYTES

    def test_non_pow2_axis_gets_the_full_axis(self):
        # 96 has no pow2 divisor above 32 in range; 8/16/32 divide it
        space = tuning.search_space("iact_rowfn",
                                    ((96, 32), (32, 64), (64, 32)))
        assert {c["block_rows"] for c in space} == {8, 16, 32}


class TestWrapperErrors:
    def test_taf_block_mismatch(self):
        x, w = _arrays("taf_matmul")
        with pytest.raises(ValueError, match="does not divide"):
            ops.taf_matmul(x, w, block_m=48, block_n=32)

    def test_taf_contraction_mismatch(self):
        x, _ = _arrays("taf_matmul")
        with pytest.raises(ValueError, match="contraction"):
            ops.taf_matmul(x, jnp.zeros((16, 32)), block_m=32, block_n=32)

    def test_iact_block_mismatch(self):
        x, w1, w2 = _arrays("iact_rowfn")
        with pytest.raises(ValueError, match="does not divide"):
            ops.iact_rowfn(x, w1, w2, block_rows=48)

    def test_pmm_block_mismatch(self):
        x, w = _arrays("perforated_matmul")
        with pytest.raises(ValueError, match="does not divide"):
            ops.perforated_matmul(x, w, block_m=48, block_n=32, block_k=32)

    def test_iact_over_vmem_budget(self):
        """Both iACT weights sit whole in VMEM: at the served FFN widths in
        float32 (96 MiB of weights) the wrapper refuses, naming the
        budget, before Mosaic would run out of VMEM. bf16 fits."""
        s = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt)  # noqa
        call = lambda x, w1, w2: ops.iact_rowfn(x, w1, w2, block_rows=128)  # noqa
        with pytest.raises(ValueError, match="VMEM_BUDGET_BYTES"):
            jax.eval_shape(call, s(2048, 2048), s(2048, 6144),
                           s(6144, 2048))
        bf = jnp.bfloat16
        jax.eval_shape(call, s(2048, 2048, dt=bf), s(2048, 6144, dt=bf),
                       s(6144, 2048, dt=bf))

    def test_attention_block_mismatch(self):
        q, k, v = _arrays("perforated_attention")
        with pytest.raises(ValueError, match="does not divide"):
            ops.flash_attention(q, k, v, block_q=48, block_kv=32)


class TestPipelineParity:
    """pipeline=True adds parallel dimension_semantics on the state-free
    grid axes; outputs and approx masks must stay BIT-equal."""

    def _check(self, out_t, out_f):
        for a, b in zip(jax.tree_util.tree_leaves(out_t),
                        jax.tree_util.tree_leaves(out_f)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("th", [0.0, 0.5, 5.0])
    def test_taf(self, th):
        x, w = _arrays("taf_matmul")
        self._check(
            ops.taf_matmul(x, w, block_m=16, block_n=16, rsd_threshold=th,
                           pipeline=True),
            ops.taf_matmul(x, w, block_m=16, block_n=16, rsd_threshold=th,
                           pipeline=False))

    @pytest.mark.parametrize("perfo", [
        None,
        PerforationParams(kind=PerforationKind.SMALL, skip=2),
        PerforationParams(kind=PerforationKind.INI, fraction=0.5),
    ])
    def test_pmm(self, perfo):
        x, w = _arrays("perforated_matmul")
        self._check(
            ops.perforated_matmul(x, w, block_m=16, block_n=16, block_k=16,
                                  perfo=perfo, pipeline=True),
            ops.perforated_matmul(x, w, block_m=16, block_n=16, block_k=16,
                                  perfo=perfo, pipeline=False))

    @pytest.mark.parametrize("fr", [None, 0.5])
    def test_attention(self, fr):
        q, k, v = _arrays("perforated_attention")
        perfo = (None if fr is None else
                 PerforationParams(kind=PerforationKind.INI, fraction=0.0))
        self._check(
            ops.perforated_attention(q, k, v, block_q=32, block_kv=32,
                                     perfo=perfo, fraction=fr,
                                     pipeline=True),
            ops.perforated_attention(q, k, v, block_q=32, block_kv=32,
                                     perfo=perfo, fraction=fr,
                                     pipeline=False))

    def test_iact_has_no_pipeline_arg(self):
        # its single grid axis is sequential (memo table carries across
        # every block): offering pipeline= would promise a variant that
        # cannot exist
        import inspect
        assert "pipeline" not in inspect.signature(
            ops.iact_rowfn).parameters


class TestAutotune:
    def test_deterministic_winner_and_hit_skips_measurement(self):
        x, w = _arrays("taf_matmul")
        calls = []

        def fake_timer(fn, args):
            calls.append(1)
            # deterministic: larger blocks "faster" (fewer grid steps)
            return 1.0 / float(np.asarray(fn(*args)).size or 1)

        c1, c2 = tuning.TuningCache(), tuning.TuningCache()
        cfg1 = tuning.autotune("taf_matmul", x, w, cache=c1,
                               measure_fn=fake_timer)
        n_after_first = len(calls)
        cfg2 = tuning.autotune("taf_matmul", x, w, cache=c2,
                               measure_fn=fake_timer)
        assert cfg1 == cfg2  # same inputs -> same winner
        # cache hit: no new measurements, same config back
        cfg3 = tuning.autotune("taf_matmul", x, w, cache=c1,
                               measure_fn=fake_timer)
        assert cfg3 == cfg1
        assert len(calls) == 2 * n_after_first

    def test_measure_false_uses_cost_model_ranking(self):
        x, w = _arrays("taf_matmul")
        cache = tuning.TuningCache()
        cfg = tuning.autotune("taf_matmul", x, w, cache=cache,
                              measure=False)
        assert tuning.validate_config(
            "taf_matmul", tuning.operand_shapes((x, w)), cfg) is None
        (entry,) = cache.entries.values()
        assert entry["measured"] == 0

    def test_cache_roundtrip_and_entry_validity(self, tmp_path):
        x, w = _arrays("taf_matmul")
        path = str(tmp_path / "cache.json")
        cache = tuning.TuningCache(path=path)
        tuning.autotune("taf_matmul", x, w, cache=cache, measure=False)
        loaded = tuning.TuningCache.load(path)
        assert loaded.entries == cache.entries
        for key, entry in loaded.entries.items():
            assert tuning.validate_entry(key, entry) is None

    def test_attention_key_uses_canonical_operands(self):
        # v mirrors k: the cache key must be (q, k) so `ops` lookups
        # (which pass two operands) hit entries tuned from three
        q, k, v = _arrays("perforated_attention")
        cache = tuning.TuningCache()
        cfg = tuning.autotune("perforated_attention", q, k, v, cache=cache,
                              measure=False)
        hit = tuning.tuned_config(
            "perforated_attention", tuning.operand_shapes((q, k)),
            cache=cache)
        assert hit == cfg


class TestOpsResolution:
    def test_none_blocks_resolve_from_ambient_cache(self):
        x, w = _arrays("taf_matmul")
        cache = tuning.TuningCache()
        key = tuning.cache_key("taf_matmul", ((128, 32), (32, 32)),
                               "float32", tuning.current_machine_name(),
                               tuning.current_substrate())
        cache.put(key, {"config": {"block_m": 64, "block_n": 16}})
        tuning.set_default_cache(cache)
        b = ops._resolve_blocks("taf_matmul", (x, w), x.dtype,
                                block_m=None, block_n=None)
        assert b == {"block_m": 64, "block_n": 16}
        # explicit ints always win over the cache
        b = ops._resolve_blocks("taf_matmul", (x, w), x.dtype,
                                block_m=32, block_n=32)
        assert b == {"block_m": 32, "block_n": 32}

    def test_miss_falls_back_to_historical_defaults(self):
        x = jnp.zeros((256, 256), jnp.float32)
        b = ops._resolve_blocks("perforated_matmul", (x, x), x.dtype,
                                block_m=None, block_n=None, block_k=None)
        assert b == tuning.FALLBACK_BLOCKS["perforated_matmul"]

    def test_zero_recompiles_across_threshold_sweep_with_tuned_blocks(self):
        # tuned geometry must not break the one-compile-per-structural-
        # group contract: 16 thresholds through cache-resolved blocks
        from repro.kernels.taf_matmul import taf_matmul as taf_jit
        x, w = _arrays("taf_matmul")
        cache = tuning.TuningCache()
        key = tuning.cache_key("taf_matmul", ((128, 32), (32, 32)),
                               "float32", tuning.current_machine_name(),
                               tuning.current_substrate())
        cache.put(key, {"config": {"block_m": 32, "block_n": 16}})
        tuning.set_default_cache(cache)
        jax.block_until_ready(ops.taf_matmul(x, w, rsd_threshold=0.1)[0])
        before = taf_jit._cache_size()
        for th in np.linspace(0.05, 2.0, 16):
            jax.block_until_ready(
                ops.taf_matmul(x, w, rsd_threshold=float(th))[0])
        assert taf_jit._cache_size() == before


class TestTuningCacheAudit:
    """Lint rule A002 over committed tuning caches."""

    def _audit(self, monkeypatch, path):
        from repro.analysis import rules
        monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
        return rules._check_tuning_cache()

    def _write(self, path, entries):
        with open(path, "w") as f:
            json.dump({"version": 1, "entries": entries}, f)

    def _entry(self, **over):
        e = {"kernel": "taf_matmul", "shapes": [[128, 32], [32, 32]],
             "dtype": "float32", "machine": "host-sim",
             "substrate": "interpret",
             "config": {"block_m": 32, "block_n": 32}, "us": 1.0}
        e.update(over)
        return e

    def _key(self, e):
        return tuning.cache_key(e["kernel"], e["shapes"], e["dtype"],
                                e["machine"], e["substrate"])

    def test_valid_cache_is_clean(self, monkeypatch, tmp_path):
        p = tmp_path / "cache.json"
        e = self._entry()
        self._write(p, {self._key(e): e})
        assert self._audit(monkeypatch, p) == []

    def test_non_dividing_block_is_a_finding(self, monkeypatch, tmp_path):
        p = tmp_path / "cache.json"
        e = self._entry(config={"block_m": 48, "block_n": 32})
        self._write(p, {self._key(e): e})
        (f,) = self._audit(monkeypatch, p)
        assert f.rule == "A002" and "does not divide" in f.message

    def test_stale_machine_key_is_a_finding(self, monkeypatch, tmp_path):
        p = tmp_path / "cache.json"
        for machine in ("old-gpu", "measured"):
            e = self._entry(machine=machine)
            self._write(p, {self._key(e): e})
            (f,) = self._audit(monkeypatch, p)
            assert f.rule == "A002" and "no substrate maps" in f.message

    def test_hand_edited_key_is_a_finding(self, monkeypatch, tmp_path):
        p = tmp_path / "cache.json"
        e = self._entry()
        self._write(p, {self._key(e).replace("128", "256", 1): e})
        (f,) = self._audit(monkeypatch, p)
        assert "stale or hand-edited" in f.message

    def test_unreadable_cache_is_a_finding(self, monkeypatch, tmp_path):
        p = tmp_path / "cache.json"
        p.write_text("{not json")
        (f,) = self._audit(monkeypatch, p)
        assert "unreadable" in f.message

    def test_missing_cache_is_silent(self, monkeypatch, tmp_path):
        assert self._audit(monkeypatch, tmp_path / "absent.json") == []

    def test_committed_cache_passes_its_own_audit(self, monkeypatch):
        import os
        from repro.analysis import rules
        monkeypatch.delenv("REPRO_TUNING_CACHE", raising=False)
        path = tuning.default_cache_path()
        if path is None or not os.path.exists(path):
            pytest.skip("no committed tuning cache")
        assert rules._check_tuning_cache() == []
