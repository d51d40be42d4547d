"""An architecture reaches the benchmark only through its family module,
`bench.reference.<reference>` (`cells.family`): the program's field map,
its parameter tree, the operation and byte counts and the reference.
These tests check that the dense family is the code the cells ran before,
that a nested field reaches the program's config, and that a family
defined by new files alone runs through the harness and the readers."""
import json
import os
import sys
import time
import types

import pytest

from bench import cells, counts, harness, weights
from bench.reference import dense

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 33 + 17

CONFIGS = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(ROOT, "bench", "configs")) if f.endswith(".json"))
# the committed configurations of the dense family; another family's
# counts are its own and are tested beside it
DENSE = [c for c in CONFIGS
         if cells.load_config(ROOT, c)["reference"] == "dense"]
GRID = [(1, 0), (4, 1023), (32, 255), (32, 511), (128, 4095)]
PROMPTS = [1, 256, 1024, 3584]


@pytest.mark.parametrize("name", DENSE)
def test_dense_family_counts_are_the_dense_counts(name):
    conf = cells.load_config(ROOT, name)
    fam = cells.family(conf)
    assert fam is dense
    for live, pos in GRID:
        assert fam.decode_step_flops(conf, live, pos) == \
            counts.decode_step_flops(conf, live, pos)
        assert fam.decode_step_bytes(conf, live, pos) == \
            counts.decode_step_bytes(conf, live, pos)
    for p in PROMPTS:
        for batch in (1, 4, 32):
            assert fam.prefill_flops(conf, p, batch) == \
                counts.prefill_flops(conf, p, batch)


def test_dense_family_binds_without_copies():
    assert dense.program_params is weights.program_params
    assert dense.prefill_flops is counts.prefill_flops
    assert dense.decode_step_flops is counts.decode_step_flops
    assert dense.decode_step_bytes is counts.decode_step_bytes


@pytest.mark.parametrize("name", CONFIGS)
def test_every_config_maps_its_published_sizes(name):
    """Each key of the family's field map that the file holds reaches the
    program's config under its field."""
    conf = cells.load_config(ROOT, name)
    cfg = cells.program_config(conf, approx=False)
    for key, field in cells.family(conf).PROGRAM_FIELDS.items():
        if key in conf:
            value = cfg
            for part in field.split("."):
                value = getattr(value, part)
            assert value == conf[key], (key, field)


def _family(monkeypatch, name, **attrs):
    mod = types.ModuleType(f"bench.reference.{name}")
    mod.__dict__.update(attrs)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_nested_field_sets_the_nested_config(monkeypatch):
    _family(monkeypatch, "latent", PROGRAM_FIELDS={
        "hidden_size": "d_model", "kv_lora_rank": "mla.kv_lora_rank",
        "n_routed_experts": "moe.n_experts"})
    conf = {"program": "deepseek-v3-671b", "reference": "latent",
            "hidden_size": 2048, "kv_lora_rank": 256,
            "n_routed_experts": 64, "torch_dtype": "bfloat16"}
    cfg = cells.program_config(conf, approx=False)
    assert cfg.d_model == 2048
    assert cfg.mla.kv_lora_rank == 256
    assert cfg.mla.q_lora_rank == 1536        # the registry's, untouched
    assert cfg.moe.n_experts == 64
    assert cfg.moe.experts_per_token == 8
    with pytest.raises(ValueError, match="qwen3-1.7b.mla is None"):
        cells.program_config(dict(conf, program="qwen3-1.7b"),
                             approx=False)


def _doubled(monkeypatch, calls):
    """A family of new code alone: dense's model, counts doubled."""
    def program_params(seed, conf, padded_vocab):
        calls.append("program_params")
        return dense.program_params(seed, conf, padded_vocab)

    def final_hidden(*args, **kw):
        calls.append("final_hidden")
        return dense.final_hidden(*args, **kw)

    return _family(
        monkeypatch, "doubled", PROGRAM_FIELDS=dense.PROGRAM_FIELDS,
        program_params=program_params, final_hidden=final_hidden,
        head_logits=dense.head_logits,
        prefill_flops=lambda *a: 2 * dense.prefill_flops(*a),
        decode_step_flops=lambda *a: 2 * dense.decode_step_flops(*a),
        decode_step_bytes=lambda *a: 2 * dense.decode_step_bytes(*a))


def test_new_family_runs_through_the_harness(tmp_path, monkeypatch):
    from conftest import SMOKE, make_bench_root
    calls = []
    _doubled(monkeypatch, calls)
    root = make_bench_root(tmp_path, [])
    conf = dict(SMOKE["qwen3-smoke"], name="doubled-smoke",
                reference="doubled")
    workload = "doubled-smoke.smoke-precise"
    for path, obj in (
            (("configs", "doubled-smoke.json"), conf),
            (("limits", f"{workload}.json"),
             {"max_gap_std": {"limit": 1e-3}})):
        path = os.path.join(root, "bench", *path)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": workload, "config": "doubled-smoke",
                               "traffic": "smoke-precise", "chips": 1,
                               "why": "smoke"})
    with open(path, "w") as f:
        json.dump(bench, f)
    r = harness.run_cell(root, workload, SEED, 1.0, False,
                         time.perf_counter(), require_chip=False,
                         log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert calls == ["program_params", "final_hidden"]


def _context(conf):
    ticks = [{"live": 32, "pos": 256 + i} for i in range(64)] \
        + [{"live": 3, "pos": 400}]
    return harness.MetricContext(
        trace={"window_s": 2.0, "programs": {"serve_step": {
            "device_s": 1.5}}},
        counters={}, ticks=ticks, admits=[{"requests": 32}], conf=conf,
        traffic={"engine": "precise", "prompt_len": 256},
        peaks=cells.load_peaks(ROOT, "TPU v5 lite"), chips=1)


@pytest.mark.parametrize("metric", ["step_mfu", "serve_step_roofline"])
def test_readers_count_through_the_family(monkeypatch, metric):
    _doubled(monkeypatch, [])
    reader = harness.load_reader(ROOT, metric)
    conf = cells.load_config(ROOT, "qwen3-1.7b")
    plain = reader.read(_context(conf))
    twice = reader.read(_context(dict(conf, reference="doubled")))
    assert plain > 0
    assert twice == pytest.approx(2 * plain, rel=1e-12)
