"""Serving driver: batched prefill + greedy decode loop, with the paper's
decode-time TAF approximation as a flag.

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --smoke \
      --prompt-len 32 --gen 32 --taf "memo(out:3:8:0.05)"

With --taf, each transformer layer carries a TAF state machine across decode
steps (repro.models.lm); the report prints tokens/s and the fraction of
layer-invocations skipped -- the serving analogue of the paper's speedup
metric (on TPU the skip is a genuine lax.cond fast path).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.types import parse_pragma
from repro.launch import steps as steps_mod
from repro.models import build


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--taf", default=None,
                    help='e.g. "memo(out:3:8:0.05)" -- decode-time TAF')
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.taf:
        cfg = dataclasses.replace(cfg, approx_decode=parse_pragma(args.taf))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    rng = np.random.RandomState(args.seed)
    prompts = rng.randint(0, cfg.vocab_size,
                          (args.batch, args.prompt_len)).astype(np.int32)
    max_len = args.prompt_len + args.gen
    batch = {"tokens": jnp.asarray(prompts), "max_len": max_len}
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_patch_tokens, cfg.d_model)) * 0.02,
            jnp.float32)
    if cfg.frontend == "audio_frames":
        batch["frames"] = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.max_source_positions, cfg.d_model)) * 0.02,
            jnp.float32)

    prefill = jax.jit(steps_mod.make_prefill_step(model, max_len))
    serve = jax.jit(steps_mod.make_serve_step(model), donate_argnums=(1,))

    t0 = time.time()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    out = [tokens]
    approx_hits = 0
    approx_total = 0
    t0 = time.time()
    for t in range(args.gen - 1):
        pos = jnp.int32(args.prompt_len + t)
        tokens, logits, cache = serve(params, cache, tokens, pos)
        if args.taf and "taf" in cache:
            rem = np.asarray(cache["taf"]["remaining"])
            approx_hits += int((rem > 0).sum())
            approx_total += rem.size
        out.append(tokens)
    jax.block_until_ready(tokens)
    t_decode = time.time() - t0
    gen = np.stack([np.asarray(t) for t in out], axis=1)
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"prefill: {t_prefill:.3f}s  decode: {t_decode:.3f}s "
          f"({tps:.1f} tok/s)")
    if args.taf and approx_total:
        print(f"TAF: {approx_hits}/{approx_total} layer-steps in stable "
              f"regime ({100 * approx_hits / approx_total:.1f}% skipped)")
    print("sample:", gen[0, :16])
    return gen


if __name__ == "__main__":
    main()
