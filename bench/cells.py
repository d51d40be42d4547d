"""Find a cell's pieces by name: `BENCHMARK.json`, the configuration, the
traffic mix, the QoS policy, the correctness limits and the peak table.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under `bench/`, so a later cell adds files and entries and
edits none:

    bench/configs/<config>.json     sizes as published, `reduced`, `assumed`
    bench/traffic/<mix>.json        waves, lengths, engine kind
    bench/policies/<config>.json    QoS ladder, class targets, controller
    bench/limits/<workload>.json    the numbers `correct` compares
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/peaks.json                chip peaks keyed by `device_kind`
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict

# configuration keys (published names) -> the program's ModelConfig fields
_PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
    "torch_dtype": "param_dtype",
}


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str) -> Dict:
    return _read(os.path.join(root, "BENCHMARK.json"))


def find_workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(root: str, name: str) -> Dict:
    return _read(os.path.join(root, "bench", "configs", f"{name}.json"))


def load_traffic(root: str, name: str) -> Dict:
    return _read(os.path.join(root, "bench", "traffic", f"{name}.json"))


def load_policy_doc(root: str, config_name: str) -> Dict:
    return _read(os.path.join(root, "bench", "policies",
                              f"{config_name}.json"))


def policy_path(root: str, config_name: str) -> str:
    return os.path.join(root, "bench", "policies", f"{config_name}.json")


def load_limits(root: str, workload: str) -> Dict:
    return _read(os.path.join(root, "bench", "limits", f"{workload}.json"))


def load_peaks(root: str, device_kind: str) -> Dict:
    """The peak row of `device_kind`; an unknown kind is an error."""
    table = _read(os.path.join(root, "bench", "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def program_config(conf: Dict, *, approx: bool):
    """The program's ModelConfig for a configuration file: the registry
    entry named by `program`, with every published size from the file.
    `approx` turns on the decode-time TAF the file states (QoS engines);
    otherwise decode is precise. The program computes in the served
    dtype (`torch_dtype`)."""
    from repro.configs import get_config
    from repro.core.types import ApproxSpec, Level, TAFParams, Technique
    fields = {f: conf[k] for k, f in _PROGRAM_FIELDS.items() if k in conf}
    spec = ApproxSpec()
    if approx:
        spec = ApproxSpec(Technique.TAF, Level.BLOCK,
                          taf=TAFParams(**conf["decode_taf"]))
    fields["compute_dtype"] = conf["torch_dtype"]
    return dataclasses.replace(get_config(conf["program"]), remat=False,
                               approx_decode=spec, **fields)
