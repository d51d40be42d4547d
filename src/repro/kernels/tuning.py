"""Block-shape autotuning for the Pallas kernels.

HPAC-Offload's headline numbers are *measured wall-clock* speedups; the
surveys it builds on stress that AC techniques only pay off when their
decision overhead is hidden by the memory hierarchy -- exactly what tile
sizing and DMA/compute overlap control. This module turns the repo's
hardcoded block shapes into a measured decision:

  1. **search space** -- per-kernel, divisor-valid only: power-of-two
     candidates for `block_m/block_n` (taf_matmul), `block_m/block_n/
     block_k` (perforated_matmul), `block_rows` (iact_rowfn) and
     `block_q/block_kv` (perforated_attention) that divide the operand
     geometry, bounded by a VMEM working-set budget;
  2. **cost-model pre-prune** -- every candidate is traced through
     `analysis/cost.trace_cost` (pallas_call body FLOPs x grid product)
     and ranked on the `analysis/machine` roofline profile with the grid
     step count as the invocation term: per-step dispatch overhead is what
     small blocks pay, on real hardware and (amplified) in interpret mode.
     Only the best `max_measure` candidates graduate to measurement;
  3. **measured wall-clock** -- explicit warm-up calls, then median-of-k
     timings around `jax.block_until_ready`. Measurement runs the precise
     path (knobs that never approximate), so candidates are compared on
     block geometry alone, not on data-dependent skip luck. With
     `measure=False` the tuner falls back to pure cost-model ranking
     (useful when interpret-mode Python timing is too slow to be worth
     paying -- see docs/kernels.md);
  4. **persistent cache** -- winners land in a JSON `TuningCache` keyed by
     (kernel, operand shapes, dtype, machine, substrate). A cache hit
     skips all measurement. `$REPRO_TUNING_CACHE` points at a cache file;
     otherwise the committed `benchmarks/baselines/tuning_cache.json` (if
     present) seeds the defaults that `kernels/ops.py` resolves when a
     caller leaves its block arguments None.

Tuned blocks are *semantic* for the AC masks (a TAF mask is
(M/block_m, N/block_n); iACT votes per block_rows; perforation liveness is
per block_kv), so a tuned geometry is a different workload fingerprint --
apps that pin geometry for parity keep passing explicit blocks, and
`approx_ffn.make_app(blocks="tuned")` records the resolved blocks in its
workload dict. Lint rule A002 audits committed caches: an entry whose
block shape no longer divides its recorded operand geometry, or whose
machine key is stale vs `analysis.machine.SUBSTRATE_MACHINES`, is a
finding.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

KERNELS = ("taf_matmul", "iact_rowfn", "perforated_matmul",
           "perforated_attention")

# Power-of-two block candidates; TPU-friendly (lane/sublane multiples) and
# small enough to enumerate exhaustively before the cost-model prune.
_POW2 = (8, 16, 32, 64, 128, 256, 512)

# Scoped VMEM every kernel asks Mosaic for (`CompilerParams.
# vmem_limit_bytes`), and the bound the search space and the kernel
# wrappers hold `vmem_bytes` to. Without it Mosaic applies its own default
# scope (16 MiB on v5e), well under the chip's 128 MiB of VMEM.
VMEM_BUDGET_BYTES = 96 * 2 ** 20


def compiler_params(dimension_semantics=None):
    """The Mosaic compile options every kernel passes: its grid axes'
    semantics (None = all sequential) and the scoped VMEM limit."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_BUDGET_BYTES)


# Hardcoded fallbacks: the pre-tuning defaults of kernels/ops.py. Used when
# no cache entry matches the operand shapes.
FALLBACK_BLOCKS: Dict[str, Dict[str, int]] = {
    "taf_matmul": {"block_m": 128, "block_n": 128},
    "iact_rowfn": {"block_rows": 128},
    "perforated_matmul": {"block_m": 128, "block_n": 128, "block_k": 128},
    "perforated_attention": {"block_q": 128, "block_kv": 128},
}

# config key -> (operand index, axis index) the block must divide
_BLOCK_AXES: Dict[str, Dict[str, Tuple[int, int]]] = {
    "taf_matmul": {"block_m": (0, 0), "block_n": (1, 1)},
    "iact_rowfn": {"block_rows": (0, 0)},
    "perforated_matmul": {"block_m": (0, 0), "block_n": (1, 1),
                          "block_k": (0, 1)},
    "perforated_attention": {"block_q": (0, 2), "block_kv": (1, 2)},
}

# how many leading operand shapes identify the workload in a cache key:
# attention's v mirrors k, so (q, k) is the canonical pair -- this must
# agree with what `ops._resolve_blocks` passes on lookup
_KEY_OPERANDS = {"taf_matmul": 2, "iact_rowfn": 3,
                 "perforated_matmul": 2, "perforated_attention": 2}


def key_shapes(kernel: str,
               shapes: Sequence[Sequence[int]]) -> Tuple[Tuple[int, ...],
                                                         ...]:
    """The canonical cache-key shape tuple: the leading operands that
    identify the workload (normalized to int tuples)."""
    nops = _KEY_OPERANDS.get(kernel, len(shapes))
    return tuple(tuple(int(d) for d in s) for s in shapes[:nops])


# --------------------------------------------------------------------------
# search space + validation
# --------------------------------------------------------------------------

def _pow2_divisors(n: int) -> List[int]:
    out = [b for b in _POW2 if b <= n and n % b == 0]
    return out or [int(n)]  # no pow2 divisor: the full axis is the one tile


def validate_config(kernel: str, shapes: Sequence[Sequence[int]],
                    config: Dict[str, int]) -> Optional[str]:
    """None if `config` is divisor-valid for `shapes`, else the reason.

    Shared by the search-space generator (which must emit only valid
    shapes), the kernel wrappers' error paths, and the A002 tuning-cache
    audit (a committed entry whose blocks stopped dividing the recorded
    geometry is stale).
    """
    axes = _BLOCK_AXES.get(kernel)
    if axes is None:
        return f"unknown kernel {kernel!r} (expected one of {KERNELS})"
    for key, (op, ax) in axes.items():
        if key not in config:
            return f"config is missing {key!r}"
        block = config[key]
        if not isinstance(block, int) or block <= 0:
            return f"{key}={block!r} is not a positive int"
        if op >= len(shapes) or ax >= len(shapes[op]):
            return (f"shapes {list(map(tuple, shapes))} have no operand "
                    f"{op} axis {ax} for {key}")
        dim = int(shapes[op][ax])
        if dim % block:
            return (f"{key}={block} does not divide operand axis "
                    f"{dim} (operand {op}, axis {ax})")
    extra = set(config) - set(axes)
    if extra:
        return f"config has keys {sorted(extra)} unknown to {kernel}"
    return None


def search_space(kernel: str, shapes: Sequence[Sequence[int]]
                 ) -> List[Dict[str, int]]:
    """All divisor-valid block configs for `kernel` on `shapes`, within the
    VMEM working-set budget. Deterministic order (sorted by block values).
    """
    axes = _BLOCK_AXES.get(kernel)
    if axes is None:
        raise ValueError(f"unknown kernel {kernel!r} "
                         f"(expected one of {KERNELS})")
    keys = sorted(axes)
    choices = []
    for key in keys:
        op, ax = axes[key]
        choices.append(_pow2_divisors(int(shapes[op][ax])))
    configs: List[Dict[str, int]] = []

    def rec(i, cur):
        if i == len(keys):
            cfg = dict(cur)
            if vmem_bytes(kernel, shapes, cfg) <= VMEM_BUDGET_BYTES:
                configs.append(cfg)
            return
        for b in choices[i]:
            cur[keys[i]] = b
            rec(i + 1, cur)

    rec(0, {})
    for cfg in configs:  # the generator's own contract, cheap to enforce
        err = validate_config(kernel, shapes, cfg)
        if err:
            raise AssertionError(f"search_space emitted invalid {cfg}: {err}")
    return configs


def grid_steps(kernel: str, shapes: Sequence[Sequence[int]],
               config: Dict[str, int]) -> int:
    """Grid size at `config`: the per-step dispatch/loop count the roofline
    invocation term charges (interpret mode pays it as a Python loop)."""
    if kernel == "taf_matmul":
        (m, _), (_, n) = shapes[0], shapes[1]
        return (m // config["block_m"]) * (n // config["block_n"])
    if kernel == "iact_rowfn":
        return shapes[0][0] // config["block_rows"]
    if kernel == "perforated_matmul":
        (m, k), (_, n) = shapes[0], shapes[1]
        return ((m // config["block_m"]) * (n // config["block_n"])
                * (k // config["block_k"]))
    if kernel == "perforated_attention":
        b, hq, sq, _ = shapes[0]
        skv = shapes[1][2]
        return (b * hq * (sq // config["block_q"])
                * (skv // config["block_kv"]))
    raise ValueError(f"unknown kernel {kernel!r}")


def vmem_bytes(kernel: str, shapes: Sequence[Sequence[int]],
               config: Dict[str, int], itemsize: int = 4,
               table_size: int = 4) -> int:
    """VMEM one kernel call holds at `config`: two buffers of every block
    that moves with the grid, one of a block that never moves (iACT's
    weights), scratch, and the body's f32 temporaries. Operands are
    `itemsize` bytes wide; outputs and temporaries are f32. For iact_rowfn
    at d_in = d_out = 2048 and 128 rows, compiles for a described v5e
    under the 96 MiB budget fit with d_h up to 10240 in bf16 and 5120 in
    f32, and ran out of VMEM at 11264 and 6144: this estimate puts the
    bound between each pair."""
    f = 4
    if kernel == "taf_matmul":
        k = shapes[0][1]
        bm, bn = config["block_m"], config["block_n"]
        return (2 * itemsize * (bm * k + k * bn) + 4 * f * bm * bn
                + f * (bm + 128))
    if kernel == "iact_rowfn":
        d_in, d_h = shapes[1]
        d_out = shapes[2][1]
        br = config["block_rows"]
        return (itemsize * (d_in * d_h + d_h * d_out)
                + 2 * (itemsize * br * d_in + f * br * d_out)
                + f * (br * d_h + br * table_size * d_in
                       + table_size * (d_in + d_out)))
    if kernel == "perforated_matmul":
        bm, bn, bk = config["block_m"], config["block_n"], config["block_k"]
        return 2 * itemsize * (bm * bk + bk * bn) + 3 * f * bm * bn
    if kernel == "perforated_attention":
        d = shapes[0][3]
        bq, bkv = config["block_q"], config["block_kv"]
        return (2 * itemsize * (2 * bq * d + 2 * bkv * d)
                + f * (bq * d + 2 * bq + bq * bkv))
    raise ValueError(f"unknown kernel {kernel!r}")


# --------------------------------------------------------------------------
# cost-model pre-prune
# --------------------------------------------------------------------------

def build_call(kernel: str, config: Dict[str, int],
               pipeline: bool = True) -> Callable:
    """The precise-path callable tuned/measured at `config`: knobs are set
    so no block ever approximates (TAF/iACT thresholds 0, no perforation),
    making candidates comparable on block geometry alone."""
    from . import ops
    if kernel == "taf_matmul":
        return lambda x, w: ops.taf_matmul(
            x, w, rsd_threshold=0.0, pipeline=pipeline, **config)[0]
    if kernel == "iact_rowfn":
        return lambda x, w1, w2: ops.iact_rowfn(
            x, w1, w2, threshold=0.0, **config)[0]
    if kernel == "perforated_matmul":
        return lambda x, w: ops.perforated_matmul(
            x, w, perfo=None, pipeline=pipeline, **config)
    if kernel == "perforated_attention":
        return lambda q, k, v: ops.flash_attention(
            q, k, v, pipeline=pipeline, **config)
    raise ValueError(f"unknown kernel {kernel!r}")


def predict_time_s(kernel: str, arrays: Sequence, config: Dict[str, int],
                   machine=None, pipeline: bool = True) -> float:
    """Roofline-predicted seconds at `config`: traced FLOPs/bytes through
    `analysis.cost.trace_cost`, with the grid step count as the invocation
    term so per-step dispatch overhead penalizes small blocks."""
    from repro.analysis.cost import trace_cost
    from repro.analysis.machine import get_machine
    mp = get_machine(machine if machine is not None
                     else current_machine_name())
    shapes = operand_shapes(arrays)
    cv = trace_cost(build_call(kernel, config, pipeline=pipeline), *arrays)
    steps = grid_steps(kernel, shapes, config)
    return mp.time_s(cv.flops, cv.bytes, invocations=float(steps))


# --------------------------------------------------------------------------
# measurement
# --------------------------------------------------------------------------

def measure_s(fn: Callable, *args, warmup: int = 2, repeats: int = 5
              ) -> float:
    """Median-of-k wall-clock seconds: explicit warm-up calls absorb
    compile + first-dispatch, then each repeat blocks on the result.
    Thin wrapper over the shared `obs.timing.measure` (same semantics;
    this name is the tuner's historical entry point)."""
    from repro.obs.timing import measure
    return measure(fn, *args, warmup=max(1, warmup),
                   repeats=max(1, repeats), stat="median",
                   span="tuning.measure").seconds


# --------------------------------------------------------------------------
# the persistent cache
# --------------------------------------------------------------------------

def current_substrate() -> str:
    """"mosaic" when the kernels compile for TPU, "interpret" on hosts."""
    from . import ops
    return "mosaic" if ops.ON_TPU else "interpret"


def current_machine_name() -> str:
    """The registered roofline profile of the running device, from its
    `device_kind` (tuning caches key on registered names so committed
    caches lint cleanly -- the process-local "measured" profile sharpens
    predictions but is not a stable cache key across machines)."""
    from repro.analysis.machine import device_machine
    return device_machine()


def operand_shapes(arrays: Sequence) -> Tuple[Tuple[int, ...], ...]:
    return tuple(tuple(int(d) for d in a.shape) for a in arrays)


def cache_key(kernel: str, shapes: Sequence[Sequence[int]], dtype: str,
              machine: str, substrate: str) -> str:
    s = "x".join(".".join(str(int(d)) for d in shp) for shp in shapes)
    return f"{kernel}|{s}|{dtype}|{machine}|{substrate}"


def validate_entry(key: str, entry: Dict) -> Optional[str]:
    """None if a cache entry is internally consistent, else the reason.
    Checks: known kernel, divisor-valid config for the recorded shapes,
    and that the entry's key fields re-derive its cache key (a hand-edited
    or stale entry fails here)."""
    kernel = entry.get("kernel")
    if kernel not in KERNELS:
        return f"unknown kernel {kernel!r}"
    shapes = entry.get("shapes")
    config = entry.get("config")
    if not shapes or not isinstance(config, dict):
        return "entry is missing shapes/config"
    err = validate_config(kernel, shapes, config)
    if err:
        return err
    rekey = cache_key(kernel, shapes, entry.get("dtype", ""),
                      entry.get("machine", ""), entry.get("substrate", ""))
    if rekey != key:
        return (f"entry fields re-derive key {rekey!r} but it is stored "
                f"under {key!r} (stale or hand-edited)")
    return None


class TuningCache:
    """A {cache_key: entry} JSON store. Entries record everything needed to
    re-validate them (kernel, shapes, dtype, machine, substrate, config)
    plus the winning measurement."""

    def __init__(self, path: Optional[str] = None,
                 entries: Optional[Dict[str, Dict]] = None):
        self.path = path
        self.entries: Dict[str, Dict] = dict(entries or {})

    @classmethod
    def load(cls, path: str) -> "TuningCache":
        with open(path) as f:
            doc = json.load(f)
        return cls(path=path, entries=doc.get("entries", {}))

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if not path:
            raise ValueError("TuningCache has no path to save to")
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"version": 1,
                       "entries": {k: self.entries[k]
                                   for k in sorted(self.entries)}},
                      f, indent=1, sort_keys=True)
        self.path = path
        return path

    def get(self, key: str) -> Optional[Dict]:
        return self.entries.get(key)

    def put(self, key: str, entry: Dict) -> None:
        self.entries[key] = entry

    def __len__(self) -> int:
        return len(self.entries)


def default_cache_path() -> Optional[str]:
    """$REPRO_TUNING_CACHE, else the committed baseline cache (if any)."""
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    # this file lives at <root>/src/repro/kernels/tuning.py
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    p = os.path.join(root, "benchmarks", "baselines", "tuning_cache.json")
    return p if os.path.exists(p) else None


_DEFAULT_CACHE: Optional[TuningCache] = None


def default_cache(reload: bool = False) -> TuningCache:
    """The process-ambient cache `kernels/ops.py` consults for None block
    defaults. Loaded lazily from `default_cache_path()`; empty when none."""
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None or reload:
        p = default_cache_path()
        _DEFAULT_CACHE = (TuningCache.load(p) if p and os.path.exists(p)
                          else TuningCache())
    return _DEFAULT_CACHE


def set_default_cache(cache: Optional[TuningCache]) -> None:
    """Install (or, with None, drop back to lazy-loading) the ambient
    cache. Tests use this to pin tuned defaults without touching disk."""
    global _DEFAULT_CACHE
    _DEFAULT_CACHE = cache


def tuned_config(kernel: str, shapes: Sequence[Sequence[int]],
                 dtype: str = "float32", machine: Optional[str] = None,
                 substrate: Optional[str] = None,
                 cache: Optional[TuningCache] = None
                 ) -> Optional[Dict[str, int]]:
    """Pure cache lookup (never measures): the tuned block config for this
    exact (kernel, shapes, dtype, machine, substrate), or None on miss."""
    cache = cache if cache is not None else default_cache()
    if not cache.entries:
        return None
    key = cache_key(kernel, key_shapes(kernel, shapes), str(dtype),
                    machine or current_machine_name(),
                    substrate or current_substrate())
    entry = cache.get(key)
    return dict(entry["config"]) if entry else None


# --------------------------------------------------------------------------
# the autotuner
# --------------------------------------------------------------------------

def autotune(kernel: str, *arrays, cache: Optional[TuningCache] = None,
             machine=None, substrate: Optional[str] = None,
             max_measure: int = 6, warmup: int = 2, repeats: int = 5,
             pipeline: bool = True, measure: bool = True,
             measure_fn: Optional[Callable] = None,
             log: Optional[Callable[[str], None]] = None) -> Dict[str, int]:
    """Tune `kernel`'s block shapes for these operands; returns the config.

    Flow: cache hit -> return immediately (no tracing, no measurement).
    Miss -> enumerate the divisor-valid search space, rank every candidate
    on the roofline cost model, measure the top `max_measure` wall-clock
    (or, with `measure=False`, crown the cost-model winner outright), and
    persist the result. `measure_fn(fn, args) -> seconds` overrides the
    timer (tests inject deterministic ones).
    """
    from repro.analysis.machine import get_machine
    mp = get_machine(machine if machine is not None
                     else current_machine_name())
    sub = substrate or current_substrate()
    shapes = key_shapes(kernel, operand_shapes(arrays))
    dtype = str(arrays[0].dtype)
    from repro import obs
    cache = cache if cache is not None else default_cache()
    key = cache_key(kernel, shapes, dtype, mp.name, sub)
    hit = cache.get(key)
    if hit is not None:
        obs.count("tuning.cache_hits")
        return dict(hit["config"])
    obs.count("tuning.cache_misses")

    space = search_space(kernel, shapes)
    if not space:
        raise ValueError(f"empty search space for {kernel} on "
                         f"{list(map(tuple, shapes))}")
    ranked = sorted(
        ((predict_time_s(kernel, arrays, cfg, machine=mp,
                         pipeline=pipeline), i, cfg)
         for i, cfg in enumerate(space)),
        key=lambda t: (t[0], t[1]))
    candidates = [cfg for _, _, cfg in ranked[:max(1, max_measure)]]
    predicted_us = {json.dumps(cfg, sort_keys=True): t * 1e6
                    for t, _, cfg in ranked}

    if measure:
        timer = measure_fn or (
            lambda fn, args: measure_s(fn, *args, warmup=warmup,
                                       repeats=repeats))
        from repro.obs import trace
        timed = []
        for cfg in candidates:
            with trace.span("tuning.measure_config", kernel=kernel,
                            config=dict(cfg)):
                s = float(timer(build_call(kernel, cfg, pipeline=pipeline),
                                arrays))
            timed.append((s, cfg))
            if log:
                log(f"{kernel} {cfg}: {s * 1e6:.1f}us")
        best_s, best = min(timed, key=lambda t: t[0])
        measured = len(timed)
    else:  # cost-model ranking fallback: no wall-clock at all
        best_s, best = ranked[0][0], candidates[0]
        measured = 0

    entry = {
        "kernel": kernel,
        "shapes": [list(s) for s in shapes],
        "dtype": dtype,
        "machine": mp.name,
        "substrate": sub,
        "config": dict(best),
        "us": round(best_s * 1e6, 3),
        "predicted_us": round(
            predicted_us[json.dumps(best, sort_keys=True)], 3),
        "pipeline": bool(pipeline),
        "candidates": len(space),
        "measured": measured,
    }
    cache.put(key, entry)
    if cache.path:
        cache.save()
    return dict(best)
