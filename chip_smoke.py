#!/usr/bin/env python3
"""Bring-up smoke test: the main path on one TPU chip, end to end.

    python chip_smoke.py              # one chip: kernels, Pallas sweep, serving
    python chip_smoke.py --chips 4    # the data-parallel serving mesh only

Run it from the repository root. One chip, by default:

  1. device   -- JAX version, platform, device kind and count; anything but
                 a TPU exits 1 before any phase runs;
  2. kernels  -- the four Pallas kernels through `repro.kernels.ops` with
                 `interpret=False`, in every mode, at the served model's
                 widths; each compiled program must hold a `tpu_custom_call`
                 and match its `kernels/ref.py` oracle;
  3. pallas   -- a short `harness.sweep` of the `approx_ffn` app on the
                 pallas substrate through its batched runner (the serial
                 fallback is an error), checked against the host substrate;
  4. serving  -- `qwen3-1.7b` at its published widths with bf16 weights from
                 `--seed`: a decode-TAF calibration sweep builds the QoS
                 ladder, then a precise `ServingEngine` and a QoS-controlled
                 one each serve the same requests, all admitted on the first
                 tick. The precise engine's tokens are checked against the
                 model's float32 forward pass without a cache.

`--chips 4` runs only the data-parallel engine on four chips against the
same engine on one device (four logical shards each), on the same requests
and weights.

Every phase runs in this one process, and a wrong result raises: the
script then exits non-zero. Only when every phase passed is the last line
of stdout the JSON device record
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
JAX's compile cache goes to `$JAX_COMPILATION_CACHE_DIR` when set, else to
`.jax_cache/` here.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))

# the served model's FFN widths: d_model x d_ff
M, K, N = 2048, 2048, 6144
BLOCK = 128
# qwen3-1.7b attention: 16 query heads over 8 KV heads of 128, 4096 positions
ATTN_Q = (1, 16, 4096, 128)
ATTN_KV = (1, 8, 4096, 128)
IACT_TABLE = 4

# serving: 8 requests of 128 prompt tokens and 32 new tokens on 8 slots
N_REQUESTS = 8
PROMPT_LEN = 128
GEN = 32
MAX_LEN = 512
CALIBRATION_THRESHOLDS = (0.02, 0.1, 0.3)
QOS_TARGET = 0.10

# precise tokens vs the float32 forward pass, teacher-forced on the engine's
# own sequence: every token within NEAR_TIE logit deviations of the float32
# maximum, and at least MIN_AGREE of them the float32 argmax itself
NEAR_TIE = 0.25
MIN_AGREE = 0.5


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernel_cases():
    """(name, kind, call, operand shapes) for every kernel and mode the
    smoke runs. `call` goes through `repro.kernels.ops` with
    `interpret=False`; operands are bf16."""
    from repro.core.types import PerforationKind, PerforationParams
    from repro.kernels import ops

    ini = PerforationParams(PerforationKind.INI, fraction=0.25)
    small = PerforationParams(PerforationKind.SMALL, skip=2)
    mm = ((M, K), (K, N))
    attn = (ATTN_Q, ATTN_KV, ATTN_KV)
    cases = []
    for pipe in (True, False):
        p = f"pipeline={pipe}"
        cases += [
            (f"taf_matmul[{p}]", "taf", functools.partial(
                ops.taf_matmul, block_m=BLOCK, block_n=BLOCK, history_size=3,
                prediction_size=8, rsd_threshold=0.5, interpret=False,
                pipeline=pipe), mm),
            (f"perforated_matmul[structural,{p}]", "pmm", functools.partial(
                ops.perforated_matmul, block_m=BLOCK, block_n=BLOCK,
                block_k=BLOCK, perfo=small, interpret=False, pipeline=pipe),
             mm),
            (f"perforated_matmul[masked,{p}]", "pmm", functools.partial(
                ops.perforated_matmul, block_m=BLOCK, block_n=BLOCK,
                block_k=BLOCK, perfo=ini, fraction=0.25, interpret=False,
                pipeline=pipe), mm),
            (f"flash_attention[{p}]", "attn", functools.partial(
                ops.flash_attention, block_q=BLOCK, block_kv=BLOCK,
                interpret=False, pipeline=pipe), attn),
            (f"perforated_attention[structural,{p}]", "attn",
             functools.partial(
                 ops.perforated_attention, block_q=BLOCK, block_kv=BLOCK,
                 perfo=small, interpret=False, pipeline=pipe), attn),
            (f"perforated_attention[masked,{p}]", "attn", functools.partial(
                ops.perforated_attention, block_q=BLOCK, block_kv=BLOCK,
                perfo=ini, fraction=0.25, interpret=False, pipeline=pipe),
             attn),
        ]
    cases.append(("iact_rowfn", "iact", functools.partial(
        ops.iact_rowfn, block_rows=BLOCK, table_size=IACT_TABLE,
        threshold=0.5, interpret=False), ((M, K), (K, N), (N, K))))
    return cases


def _kernel_inputs(kind, shapes, rng):
    """float32 operands: block-correlated rows where the technique needs
    them (TAF: stable block means; iACT: each row block repeats once)."""
    import numpy as np
    if kind == "taf":
        (m, k), (_, n) = shapes
        x = np.tile(rng.randn(1, k), (m, 1)) + 0.02 * rng.randn(m, k)
        return [x, rng.randn(k, n) / np.sqrt(k)]
    if kind == "iact":
        (m, k), (_, n), _ = shapes
        distinct = rng.randn(m // (2 * BLOCK), k)
        x = np.repeat(distinct, 2 * BLOCK, axis=0)
        return [x, rng.randn(k, n) / np.sqrt(k), rng.randn(n, k) / np.sqrt(n)]
    if kind == "pmm":
        (m, k), (_, n) = shapes
        return [rng.randn(m, k), rng.randn(k, n) / np.sqrt(k)]
    return [rng.randn(*s) for s in shapes]


def _kernel_oracle(kind, call, xs):
    """The `kernels/ref.py` oracle of one case, on the same (bf16-rounded)
    operands; jnp oracles run at full float32 matmul precision."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    kw = call.keywords
    with jax.default_matmul_precision("highest"):
        if kind == "taf":
            return ref.taf_matmul_ref(
                *xs, block_m=kw["block_m"], block_n=kw["block_n"],
                history_size=kw["history_size"],
                prediction_size=kw["prediction_size"],
                rsd_threshold=kw["rsd_threshold"])
        if kind == "iact":
            return ref.iact_rowfn_ref(
                *xs, block_rows=kw["block_rows"],
                table_size=kw["table_size"], threshold=kw["threshold"])
        if kind == "pmm":
            return ref.perforated_matmul_ref(*xs, block_k=kw["block_k"],
                                             perfo=kw["perfo"])
        q, k, v = (jnp.asarray(a) for a in xs)
        group = q.shape[1] // k.shape[1]
        return ref.attention_ref(
            q, jnp.repeat(k, group, 1), jnp.repeat(v, group, 1),
            causal=True, block_kv=kw["block_kv"], perfo=kw.get("perfo"),
            out_dtype=jnp.float32)


_KERNEL_TOL = {"taf": 1e-3, "pmm": 1e-3, "iact": 2e-2, "attn": 2e-2}


def phase_kernels(seed: int) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    for name, kind, call, shapes in kernel_cases():
        xs = [jnp.asarray(a, jnp.bfloat16)
              for a in _kernel_inputs(kind, shapes, rng)]
        t0 = time.perf_counter()
        compiled = jax.jit(call).lower(*xs).compile()
        compile_s = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            raise RuntimeError(f"{name}: compiled program has no "
                               "tpu_custom_call (the kernel did not go "
                               "through Mosaic)")
        out = jax.block_until_ready(compiled(*xs))
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*xs))
        run_s = time.perf_counter() - t0
        expect = _kernel_oracle(kind, call, [np.asarray(x, np.float32)
                                             for x in xs])
        y, mask = (out if kind in ("taf", "iact") else (out, None))
        yr, mr = (expect if kind in ("taf", "iact") else (expect, None))
        y = np.asarray(y, np.float32)
        yr = np.asarray(yr, np.float32)
        if not np.isfinite(y).all():
            raise RuntimeError(f"{name}: non-finite output")
        err = float(np.abs(y - yr).max() / max(np.abs(yr).max(), 1e-30))
        line = (f"kernel {name} mosaic=True compile_s={compile_s:.3f} "
                f"run_s={run_s:.6f} max_rel_err={err:.3e}")
        if mask is not None:
            mask, mr = np.asarray(mask), np.asarray(mr)
            if not np.array_equal(mask, mr):
                raise RuntimeError(
                    f"{name}: approximation mask differs from the oracle "
                    f"in {int((mask != mr).sum())} of {mask.size} blocks")
            if mask.all() or not mask.any():
                raise RuntimeError(f"{name}: the inputs exercised only one "
                                   f"path (approximated {mask.mean():.2f})")
            line += f" approx_blocks={int(mask.sum())}/{mask.size}"
        log(line)
        if err > _KERNEL_TOL[kind]:
            raise RuntimeError(f"{name}: max relative error {err:.3e} over "
                               f"{_KERNEL_TOL[kind]:.0e}")


# ---------------------------------------------------------------------------
# pallas substrate
# ---------------------------------------------------------------------------

def _ffn_specs():
    from repro.core.harness import iact_grid, taf_grid
    from repro.core.types import (ApproxSpec, Level, PerforationKind,
                                  PerforationParams, Technique)
    return (taf_grid(h_sizes=(2,), p_sizes=(4,), thresholds=(0.05, 0.2, 1.0),
                     levels=(Level.BLOCK,))
            + iact_grid(t_sizes=(4,), thresholds=(0.05, 0.5, 5.0),
                        tables_per_block=(1,), levels=(Level.BLOCK,))
            + [ApproxSpec(Technique.PERFORATION, Level.BLOCK,
                          perforation=PerforationParams(
                              kind=PerforationKind.INI, fraction=f))
               for f in (0.25, 0.5)])


def phase_pallas_sweep() -> None:
    """The approx_ffn grid through the batched runner on the pallas
    substrate; every spec's approximation mask must equal the host
    substrate's (the ref.py oracles)."""
    import numpy as np
    from apps import approx_ffn
    from repro.core.harness import sweep

    specs = _ffn_specs()
    app = approx_ffn.make_app(substrate="pallas")
    if app.run_batch is None:
        raise RuntimeError("approx_ffn on the pallas substrate has no "
                           "batched runner")
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        recs = sweep(app, specs, repeats=1, jobs=4, substrate="pallas")
    wall = time.perf_counter() - t0
    fell_back = [str(w.message) for w in caught
                 if "falling back to the serial path" in str(w.message)]
    if fell_back:
        raise RuntimeError(f"run_batch fell back to the serial path: "
                           f"{fell_back[0]}")
    host = sweep(approx_ffn.make_app(substrate="host"), specs, repeats=1,
                 substrate="host")
    worst = 0.0
    for r, h in zip(recs, host):
        pm = np.asarray(r.extra["approx_mask"])
        hm = np.asarray(h.extra["approx_mask"])
        if not np.array_equal(pm, hm):
            raise RuntimeError(f"approx_ffn {r.spec}: pallas mask "
                               f"{pm.tolist()} != host mask {hm.tolist()}")
        if not np.isfinite(r.error):
            raise RuntimeError(f"approx_ffn {r.spec}: error {r.error}")
        worst = max(worst, abs(r.error - h.error))
    approx = sum(r.approx_fraction > 0 for r in recs)
    log(f"pallas_sweep app=approx_ffn specs={len(recs)} batched=True "
        f"fallback=0 approximated_specs={approx} masks_match_host=True "
        f"max_error_diff_vs_host={worst:.3e} wall_s={wall:.3f}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def served_config():
    """qwen3-1.7b at its published widths with decode-time TAF on."""
    from repro.configs import get_config
    from repro.core.types import ApproxSpec, Level, TAFParams, Technique
    return dataclasses.replace(
        get_config("qwen3-1.7b"), remat=False,
        approx_decode=ApproxSpec(Technique.TAF, Level.BLOCK,
                                 taf=TAFParams(history_size=2,
                                               prediction_size=4,
                                               rsd_threshold=0.5)))


def _requests(prompts, gen, classes=("default",)):
    from repro.serving import Request
    return [Request(uid=i, prompt=p, max_new_tokens=gen,
                    qos_class=classes[i % len(classes)])
            for i, p in enumerate(prompts)]


def _serve(engine, reqs):
    """Warm the engine up, submit every request before the first tick (so
    all lanes share one decode position), drain. Returns (compile_s,
    wall_s, tokens (n, gen))."""
    import numpy as np
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    stats = engine.run_until_drained()
    wall = time.perf_counter() - t0
    short = [r.uid for r in reqs if len(r.output) != r.max_new_tokens]
    if stats.finished != len(reqs) or short:
        raise RuntimeError(f"engine finished {stats.finished}/{len(reqs)} "
                           f"requests; short outputs: {short}")
    return compile_s, wall, np.asarray([r.output for r in reqs], np.int32)


def _head(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def reference_check(cfg, params, prompts, engine_tokens):
    """The precise engine's tokens against the model's float32 forward pass
    without a cache (full matmul precision). `engine_tokens` (n, g) is the
    engine's whole greedy sequence after each prompt. Returns (greedy match
    rate, teacher-forced argmax agreement, worst gap of an engine token
    below the float32 maximum in logit standard deviations)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core.types import ApproxSpec
    from repro.models import build

    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32",
                                approx_decode=ApproxSpec())
    m32 = build(cfg32)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    n, lp = prompts.shape
    g = engine_tokens.shape[1]

    def logits_at(p, toks, start, width):
        h = m32.hidden(p, {"tokens": toks})
        h = jax.lax.dynamic_slice_in_dim(h, start, width, axis=1)
        return h @ _head(cfg32, p)

    def greedy_step(p, buf, t):
        nxt = jnp.argmax(logits_at(p, buf, t, 1)[:, 0], -1).astype(jnp.int32)
        return jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, t + 1))

    def forced(p, seq, eng):
        lg = logits_at(p, seq, lp - 1, g)
        chosen = jnp.take_along_axis(lg, eng[..., None], -1)[..., 0]
        gap = (lg.max(-1) - chosen) / lg.std(-1)
        return gap, jnp.argmax(lg, -1) == eng

    with jax.default_matmul_precision("highest"):
        step = jax.jit(greedy_step)
        buf = jnp.zeros((n, lp + g), jnp.int32).at[:, :lp].set(prompts)
        for t in range(lp - 1, lp - 1 + g):
            buf = step(p32, buf, jnp.int32(t))
        ref_tokens = np.asarray(buf[:, lp:])
        eng = jnp.asarray(engine_tokens)
        seq = jnp.concatenate([jnp.asarray(prompts), eng[:, :-1]], axis=1)
        gap, agree = jax.jit(forced)(p32, seq, eng)
    greedy = float((ref_tokens == engine_tokens).mean())
    return greedy, float(np.asarray(agree).mean()), float(np.asarray(gap).max())


def phase_serving(cfg, seed: int, *, n_requests=N_REQUESTS,
                  prompt_len=PROMPT_LEN, gen=GEN, max_len=MAX_LEN,
                  calibration_gen=12) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro import qos
    from repro.core.harness import sweep
    from repro.core.types import ApproxSpec
    from repro.launch import steps as steps_mod
    from repro.models import build
    from repro.serving import ServingEngine

    log(f"serving model={cfg.name} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} tied={cfg.tie_embeddings} "
        f"params={cfg.param_dtype} slots={n_requests} max_len={max_len}")

    # 1. the QoS ladder, built as benchmarks/qos_serving.py builds it
    t0 = time.perf_counter()
    app = qos.make_decode_app(cfg, gen=calibration_gen, seed=seed,
                              metric="mcr")
    recs = sweep(app, qos.threshold_grid(cfg, CALIBRATION_THRESHOLDS),
                 repeats=1)
    del app
    policy = qos.QosPolicy.from_records(recs, metric="mcr",
                                        use_modeled=True)
    log(f"calibration thresholds={list(CALIBRATION_THRESHOLDS)} "
        f"ladder_rungs={len(policy)} wall_s={time.perf_counter() - t0:.3f} "
        + " ".join(f"th={r.spec.get('thresh')}:err={r.error:.4f}:"
                   f"skip={r.approx_fraction:.4f}" for r in recs))

    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    prompts = rng.randint(0, cfg.vocab_size,
                          (n_requests, prompt_len)).astype(np.int32)
    engine_kw = dict(slots=n_requests, max_len=max_len,
                     prompt_len=prompt_len)

    # 2. precise engine
    precise_model = build(dataclasses.replace(cfg,
                                              approx_decode=ApproxSpec()))
    eng = ServingEngine(precise_model, params, **engine_kw)
    p_compile, p_wall, p_tokens = _serve(eng, _requests(prompts, gen))
    p_stats = eng.stats
    del eng
    log(f"precise_engine requests={n_requests} tokens={p_stats.tokens_out} "
        f"ticks={p_stats.ticks} compile_s={p_compile:.3f} "
        f"wall_s={p_wall:.6f} "
        f"tokens_per_s={p_stats.tokens_out / p_wall:.3f}")

    # 3. QoS-controlled engine on the same requests
    engine_qos = qos.QosEngine(
        policy, {"default": QOS_TARGET, "batch": 10 * QOS_TARGET},
        sample_fraction=0.25, window=8,
        config=qos.ControllerConfig(min_samples=2, hold_ticks=2,
                                    fallback_hold=4))
    eng = ServingEngine(model, params, qos=engine_qos, **engine_kw)
    q_compile, q_wall, q_tokens = _serve(
        eng, _requests(prompts, gen, classes=("default", "batch")))
    q_stats = eng.stats
    summary = engine_qos.summary()
    del eng, engine_qos
    log(f"qos_engine requests={n_requests} tokens={q_stats.tokens_out} "
        f"ticks={q_stats.ticks} compile_s={q_compile:.3f} "
        f"wall_s={q_wall:.6f} tokens_per_s={q_stats.tokens_out / q_wall:.3f} "
        f"taf_skip_fraction={q_stats.taf_skip_fraction:.6f} "
        f"canary_ticks={q_stats.canary_ticks} "
        f"canary_samples={summary['canary_samples']} "
        f"knob_moves={q_stats.knob_moves} "
        f"fallback_rate={summary['fallback_rate']:.4f} "
        f"token_match_vs_precise={float((q_tokens == p_tokens).mean()):.4f}")

    # 4. precise tokens vs the float32 forward pass. The engine's first
    #    generated token comes from its prefill and is not in the request
    #    output; the same jitted prefill step recomputes it.
    prefill = jax.jit(steps_mod.make_prefill_step(precise_model, max_len))
    first = np.asarray(jnp.argmax(
        prefill(params, {"tokens": jnp.asarray(prompts)})[0], -1))
    engine_tokens = np.concatenate([first[:, None], p_tokens], axis=1)
    t0 = time.perf_counter()
    greedy, agree, gap = reference_check(cfg, params, prompts, engine_tokens)
    log(f"reference float32_full_forward greedy_match_rate={greedy:.4f} "
        f"teacher_forced_agreement={agree:.4f} "
        f"max_gap_in_logit_std={gap:.4f} "
        f"wall_s={time.perf_counter() - t0:.3f}")
    if gap > NEAR_TIE or agree < MIN_AGREE:
        raise RuntimeError(
            f"precise engine disagrees with the float32 forward pass: "
            f"agreement {agree:.4f} (need >= {MIN_AGREE}), worst token "
            f"{gap:.4f} logit std below the maximum (need <= {NEAR_TIE})")


# ---------------------------------------------------------------------------
# four chips: the data-parallel serving path
# ---------------------------------------------------------------------------

def phase_mesh(cfg, seed: int, n_devices: int = 4, *,
               n_requests=N_REQUESTS, prompt_len=PROMPT_LEN, gen=GEN,
               max_len=MAX_LEN) -> None:
    """`ServingEngine(devices=n, shards=n)` against `ServingEngine(
    devices=1, shards=n)` on the same requests and weights.

    Free-running tokens are compared and reported, not required equal:
    the two layouts round differently, and decode-time TAF turns a
    rounding difference into a different skip decision. What must agree
    is one decode step from the same state: the 1-device engine's final
    cache and tokens, stepped by each engine on its own mesh, give logits
    within NEAR_TIE logit standard deviations of each other."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.launch import steps as steps_mod
    from repro.models import build
    from repro.runtime import sharding as shardlib
    from repro.serving import ServingEngine

    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    prompts = rng.randint(0, cfg.vocab_size,
                          (n_requests, prompt_len)).astype(np.int32)
    engines = {}
    for devices in (n_devices, 1):
        eng = ServingEngine(model, params, slots=n_requests, max_len=max_len,
                            prompt_len=prompt_len, devices=devices,
                            shards=n_devices)
        compile_s, wall, tokens = _serve(eng, _requests(prompts, gen))
        spread = {name: min(len(leaf.sharding.device_set)
                            for leaf in jax.tree_util.tree_leaves(tree))
                  for name, tree in (("params", eng.params),
                                     ("cache", eng.cache),
                                     ("tokens", eng.tokens))}
        log(f"mesh_engine devices={devices} shards={n_devices} "
            f"mesh_shape={eng.mesh_shape} compile_s={compile_s:.3f} "
            f"wall_s={wall:.6f} "
            f"tokens_per_s={eng.stats.tokens_out / wall:.3f} "
            f"taf_skip_fraction={eng.stats.taf_skip_fraction:.6f} "
            f"state_devices_per_leaf={spread}")
        if any(v != devices for v in spread.values()):
            raise RuntimeError(f"devices={devices}: engine state is not on "
                               f"every device (fewest per leaf: {spread})")
        engines[devices] = (eng, tokens)

    (_, t_n), (one, t_1) = engines[n_devices], engines[1]
    same = t_n == t_1
    first = [int(np.argmin(row)) if not row.all() else gen for row in same]
    logits = {}
    for devices, (eng, _) in engines.items():
        specs = shardlib.decode_partition_specs(eng.mesh, one.cache,
                                                n_requests)
        cache = jax.tree.map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(eng.mesh, spec)), one.cache, specs)
        tokens = jax.device_put(one.tokens, NamedSharding(
            eng.mesh, shardlib.batch_spec(eng.mesh)))
        step = jax.jit(steps_mod.make_sharded_serve_step(
            model, eng.mesh, n_devices, n_requests))
        logits[devices] = np.asarray(
            step(eng.params, cache, tokens, jnp.int32(prompt_len + gen))[1],
            np.float32)
    l_n, l_1 = logits[n_devices], logits[1]
    diff = float(np.abs(l_n - l_1).max())
    diff_std = diff / float(l_1.std(-1).mean())
    step_agree = float((l_n.argmax(-1) == l_1.argmax(-1)).mean())
    log(f"mesh_vs_one_device token_match_rate={float(same.mean()):.4f} "
        f"first_mismatch_per_request={first} "
        f"one_step_max_logit_diff={diff:.6e} "
        f"one_step_max_logit_diff_in_std={diff_std:.4f} "
        f"one_step_argmax_agreement={step_agree:.4f}")
    if diff_std > NEAR_TIE:
        raise RuntimeError(
            f"one decode step from the same state differs by {diff_std:.4f} "
            f"logit std between {n_devices} devices and 1 (need <= "
            f"{NEAR_TIE})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels, Pallas sweep and serving on one chip; "
                    "4: only the data-parallel serving mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and inputs")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device jax={jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no repository around {ROOT}", file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "examples")]
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"compile_cache {enable_compile_cache(ROOT)}")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(served_config(), args.seed)
    else:
        phase_kernels(args.seed)
        phase_pallas_sweep()
        phase_serving(served_config(), args.seed)
    stats = dev.memory_stats() or {}
    log(f"done wall_s={time.perf_counter() - t0:.3f} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
