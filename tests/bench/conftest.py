"""Fixtures of the benchmark's tests: a benchmark defined only in a
temporary directory, at smoke size, for both model families."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE = {
    "qwen3-smoke": {
        "program": "qwen3-1.7b", "reference": "dense", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "vocab_size": 256, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": True, "attention_bias": False,
        "qk_norm": True, "torch_dtype": "float32",
        "decode_taf": {"history_size": 2, "prediction_size": 4,
                       "rsd_threshold": 0.5}},
    "qwen1.5-smoke": {
        "program": "qwen1.5-4b", "reference": "dense", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "vocab_size": 256, "rope_theta": 5000000.0, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "attention_bias": True,
        "qk_norm": False, "torch_dtype": "float32",
        "decode_taf": {"history_size": 2, "prediction_size": 4,
                       "rsd_threshold": 0.5}},
}

TRAFFIC = {
    "smoke-precise": {"engine": "precise", "slots": 4, "prompt_len": 8,
                      "max_len": 24,
                      "output": {"dist": "lognormal", "median": 6,
                                 "sigma": 0.6, "min": 3, "max": 16},
                      "classes": ["default"], "sample_requests": 3},
    # the control's test: more served tokens in the sample, so that the
    # fp8 control's first tokens depart from the reference's on some
    "smoke-control": {"engine": "precise", "slots": 8, "prompt_len": 8,
                      "max_len": 40,
                      "output": {"dist": "lognormal", "median": 14,
                                 "sigma": 0.6, "min": 8, "max": 32},
                      "classes": ["default"], "sample_requests": 8},
    "smoke-qos": {"engine": "qos", "slots": 4, "prompt_len": 8,
                  "max_len": 24,
                  "output": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                             "min": 3, "max": 16},
                  "classes": ["default", "batch"], "sample_requests": 3},
}

# float32 smoke weights and compute: the engine stays within rounding of
# the reference
SMOKE_LIMIT = 1e-3


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_bench_root(root, cells, per_layer=()):
    """A checkout-like directory holding only data: BENCHMARK.json, the
    configs, traffic files, limits, a policy per config, the peak table
    and the named metric readers. `cells` is [(config, traffic)]."""
    for name, conf in SMOKE.items():
        _write(os.path.join(root, "bench", "configs", f"{name}.json"),
               dict(conf, name=name))
        _write(os.path.join(root, "bench", "policies", f"{name}.json"),
               {"version": 1, "app": "taf_decode", "metric": "mcr",
                "substrate": None, "use_modeled": True,
                "entries": [{"spec": {"technique": "taf", "level": "block",
                                      "hSize": 2, "pSize": 4,
                                      "thresh": 0.3},
                             "error": 0.5, "speedup": 1.0,
                             "modeled_speedup": 1.5}],
                "targets": {"default": 0.10, "batch": 1.0},
                "monitor": {"sample_fraction": 0.25, "window": 8},
                "controller": {"min_samples": 2, "hold_ticks": 2,
                               "fallback_hold": 4}})
    for name, t in TRAFFIC.items():
        _write(os.path.join(root, "bench", "traffic", f"{name}.json"), t)
    shutil.copy(os.path.join(ROOT, "bench", "peaks.json"),
                os.path.join(root, "bench", "peaks.json"))
    os.makedirs(os.path.join(root, "bench", "metrics"), exist_ok=True)
    for m in per_layer:
        shutil.copy(os.path.join(ROOT, "bench", "metrics", f"{m}.py"),
                    os.path.join(root, "bench", "metrics", f"{m}.py"))
    workloads = []
    for conf, traffic in cells:
        name = f"{conf}.{traffic}"
        workloads.append({"name": name, "config": conf, "traffic": traffic,
                          "chips": 1, "why": "smoke"})
        _write(os.path.join(root, "bench", "limits", f"{name}.json"),
               {"max_gap_std": {"limit": SMOKE_LIMIT}})
    _write(os.path.join(root, "BENCHMARK.json"), {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1, "configs": [], "workloads": workloads,
        "end_to_end": [
            {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "tpot_ms_p95", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": m, "unit": "%", "better": "higher",
                       "source": "device_trace", "layer": "device",
                       "moves": "tokens_per_s"} for m in per_layer]})
    return str(root)


@pytest.fixture
def bench_root(tmp_path):
    return make_bench_root(
        tmp_path, [(c, t) for c in SMOKE for t in TRAFFIC])
