"""Find a cell's pieces by name: `BENCHMARK.json`, the configuration, its
architecture family, the traffic mix, the QoS policy, the correctness
limits and the peak table.

Everything that belongs to one configuration, traffic mix, architecture
or metric is a file of its own under `bench/`, so a later cell adds files
and entries and edits none:

    bench/configs/<config>.json     sizes as published, `reduced`, `assumed`
    bench/reference/<family>.py     the architecture, named by the config's
                                    `reference` key (see `family`)
    bench/traffic/<mix>.json        waves, lengths, engine kind
    bench/policies/<config>.json    QoS ladder, class targets, controller
    bench/limits/<workload>.json    the numbers `correct` compares
    bench/metrics/<metric>.py       one reader per per-layer metric
    bench/peaks.json                chip peaks keyed by `device_kind`

A new architecture is its configuration file with `"reference":
"<family>"`, its family module, its traffic, limits and metric readers,
and its entries in BENCHMARK.json.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Dict

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


def least_time_s(flops: float, nbytes: float, peaks: Dict) -> float:
    """Roofline floor: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def family(conf: Dict):
    """The architecture family of a configuration: the module
    `bench.reference.<conf["reference"]>`, the one place the benchmark
    learns an architecture from. It holds

        PROGRAM_FIELDS        published keys -> the program's ModelConfig
                              fields; a dotted field ("mla.kv_lora_rank")
                              is a field of a nested config
        program_params(seed, conf, padded_vocab)
                              the program's parameter tree from the seed
        prefill_flops(conf, prompt_len, batch)
        decode_step_flops(conf, live, position)
        decode_step_bytes(conf, live, position)
                              operations and least bytes, from the shapes
        final_hidden(conf, seed, tokens, precision)
        head_logits(conf, seed, rows, precision)
                              the plain float32 reference and its control
    """
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def _replace(base, fields: Dict, where: str):
    """`base` with `fields` set; a dotted name sets a field of the nested
    config, which the registry entry must have."""
    flat, nested = {}, {}
    for name, value in fields.items():
        head, dot, rest = name.partition(".")
        if dot:
            nested.setdefault(head, {})[rest] = value
        else:
            flat[name] = value
    for head, sub in nested.items():
        inner = getattr(base, head)
        if inner is None:
            raise ValueError(f"{where}.{head} is None in the registry, so "
                             f"{sorted(sub)} cannot be set on it")
        flat[head] = _replace(inner, sub, f"{where}.{head}")
    return dataclasses.replace(base, **flat)


def program_config(conf: Dict, *, approx: bool):
    """The program's ModelConfig for a configuration file: the registry
    entry named by `program`, with every published size from the file
    that the family's `PROGRAM_FIELDS` maps. `approx` turns on the
    decode-time TAF the file states (QoS engines); otherwise decode is
    precise. The program computes in the served dtype (`torch_dtype`)."""
    from repro.configs import get_config
    from repro.core.types import ApproxSpec, Level, TAFParams, Technique
    fields = {f: conf[k] for k, f in family(conf).PROGRAM_FIELDS.items()
              if k in conf}
    spec = ApproxSpec()
    if approx:
        spec = ApproxSpec(Technique.TAF, Level.BLOCK,
                          taf=TAFParams(**conf["decode_taf"]))
    fields.update(remat=False, approx_decode=spec,
                  compute_dtype=conf["torch_dtype"])
    return _replace(get_config(conf["program"]), fields, conf["program"])
