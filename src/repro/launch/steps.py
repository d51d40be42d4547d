"""Step functions: train_step (fwd + bwd + AdamW), prefill, serve(decode).

These are the functions the dry-run lowers and the drivers execute.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.lm import Model
from repro.optim import adamw
from repro.optim import schedule as sched


def make_train_step(model: Model, opt_cfg: adamw.AdamWConfig,
                    schedule_fn: Optional[Callable] = None,
                    schedule_kwargs: Optional[Dict] = None) -> Callable:
    schedule_fn = schedule_fn or sched.constant
    schedule_kwargs = schedule_kwargs or {}

    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return model.loss(p, batch)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        lr_scale = schedule_fn(opt_state.step, **schedule_kwargs)
        new_params, new_opt, om = adamw.update(opt_cfg, grads, opt_state,
                                               params, lr_scale)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def make_train_step_accum(model: Model, opt_cfg: adamw.AdamWConfig,
                          accum_steps: int,
                          schedule_fn: Optional[Callable] = None,
                          schedule_kwargs: Optional[Dict] = None) -> Callable:
    """Gradient-accumulated train step: the global batch is split into
    `accum_steps` microbatches scanned sequentially; activation memory drops
    ~accum_steps x (the remedy for train cells whose per-device working set
    exceeds HBM -- EXPERIMENTS.md section Dry-run), and on TPU the per-bucket
    gradient reduction overlaps the next microbatch's compute. Also the
    elastic-scaling knob: `runtime.elastic.accum_steps_for` keeps the global
    batch constant across mesh reshapes."""
    schedule_fn = schedule_fn or sched.constant
    schedule_kwargs = schedule_kwargs or {}

    def train_step(params, opt_state, batch):
        def to_micro(x):
            b = x.shape[0]
            assert b % accum_steps == 0, (b, accum_steps)
            return x.reshape((accum_steps, b // accum_steps) + x.shape[1:])

        micro = {k: to_micro(v) for k, v in batch.items()}

        def body(carry, mb):
            g_acc, loss_acc = carry
            (loss, _), grads = jax.value_and_grad(
                lambda p: model.loss(p, mb), has_aux=True)(params)
            g_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / accum_steps,
                g_acc, grads)
            return (g_acc, loss_acc + loss / accum_steps), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (grads, loss), _ = jax.lax.scan(body, (zeros, jnp.float32(0)), micro)
        lr_scale = schedule_fn(opt_state.step, **schedule_kwargs)
        new_params, new_opt, om = adamw.update(opt_cfg, grads, opt_state,
                                               params, lr_scale)
        om["loss"] = loss
        return new_params, new_opt, om

    return train_step


def make_prefill_step(model: Model, max_len: int) -> Callable:
    def prefill_step(params, batch):
        full = dict(batch)
        full["max_len"] = max_len
        return model.prefill(params, full)

    return prefill_step


def make_serve_step(model: Model) -> Callable:
    """One-token decode: (params, cache, tokens (B,), pos) ->
    (next_tokens, logits, new_cache).

    The step consumes the cache passed to it: `new_cache` is that cache
    with each layer's new rows written at `pos`, and nothing else of it is
    copied. Jit it with `donate_argnums=(1,)` (as `ServingEngine` does) and
    the write happens in place, so the caller must not use the old cache
    afterwards; without donation XLA copies the whole cache into a new
    buffer on every step."""

    def serve_step(params, cache, tokens, pos):
        logits, new_cache = model.decode_step(params, cache, tokens, pos)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, logits, new_cache

    return serve_step


def make_sharded_serve_step(model: Model, mesh, n_shards: int,
                            batch_size: int) -> Callable:
    """The serve step shard_map'd over the mesh's data axes: request lanes
    are data-parallel, and the decode cache's per-shard state, the TAF
    detector and the expert row counter (see `models.lm.shard_decode_state`),
    carries a leading LOGICAL-shard dim that is vmapped inside each device,
    so:

      * n_shards is decoupled from the device count (any multiple of the
        mesh's data extent): the same engine config runs on 1 device and
        on the CI 8-device mesh with bit-identical outputs -- per-shard
        compute has no cross-shard collectives, and vmap of the per-shard
        step produces the same values regardless of how shards are packed
        onto devices;
      * each shard's TAF threshold is an independent traced knob: the QoS
        plane tightens/loosens individual shards by writing one row of the
        (n_shards, n_layers) threshold leaf -- never a recompile;
      * the TAF stability statistic (a batch mean) is computed over each
        shard's OWN lanes, so one shard's regime change cannot flip
        another shard's skip decisions.

    Call with a cache that has been through `shard_decode_state`.
    Signature and cache contract match `make_serve_step`: (params, cache,
    tokens (B,), pos) -> (next_tokens, logits, new_cache), the step
    consumes the cache passed to it, and jitted with `donate_argnums=(1,)`
    it writes the new rows in place.
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.runtime import sharding as shardlib

    serve = make_serve_step(model)
    da = shardlib.data_axes(mesh)
    if not da:
        raise ValueError("mesh has no data axis (expected 'data'/'pod')")
    daxis = da if len(da) > 1 else da[0]
    n_data = 1
    for a in da:
        n_data *= int(mesh.shape[a])
    if n_shards % n_data:
        raise ValueError(f"n_shards ({n_shards}) must be a multiple of the "
                         f"mesh's data extent ({n_data})")
    if batch_size % n_shards:
        raise ValueError(f"batch_size ({batch_size}) must divide evenly "
                         f"into {n_shards} shards")
    local_shards = n_shards // n_data
    lanes = batch_size // n_shards
    tok_spec = shardlib.batch_spec(mesh)

    def sharded_step(params, cache, tokens, pos):
        paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
        kinds = [shardlib.decode_shard_axis(p, l.shape, batch_size)
                 for p, l in paths_leaves]
        cache_specs = shardlib.decode_partition_specs(mesh, cache,
                                                      batch_size)
        # vmap axis per leaf: the shard dim's position (None = broadcast)
        vmap_axes = jax.tree_util.tree_unflatten(
            treedef, [None if k is None else k[1] for k in kinds])

        def local_step(params, cache, tokens, pos):
            # split each local leaf's lane dim (local_shards * lanes) into
            # an explicit shard dim for vmap; detector-state leaves already
            # lead with it
            def split(leaf, kind):
                if kind is None or kind[0] == "state":
                    return leaf
                ax, sh = kind[1], leaf.shape
                return leaf.reshape(sh[:ax] + (local_shards, lanes)
                                    + sh[ax + 1:])

            def merge(leaf, kind):
                if kind is None or kind[0] == "state":
                    return leaf
                ax, sh = kind[1], leaf.shape
                return leaf.reshape(sh[:ax] + (local_shards * lanes,)
                                    + sh[ax + 2:])

            leaves = treedef.flatten_up_to(cache)
            c = jax.tree_util.tree_unflatten(
                treedef, [split(l, k) for l, k in zip(leaves, kinds)])
            step = jax.vmap(serve, in_axes=(None, vmap_axes, 0, None),
                            out_axes=(0, 0, vmap_axes))
            ntok, logits, ncache = step(
                params, c, tokens.reshape(local_shards, lanes), pos)
            nleaves = treedef.flatten_up_to(ncache)
            ncache = jax.tree_util.tree_unflatten(
                treedef, [merge(l, k) for l, k in zip(nleaves, kinds)])
            return (ntok.reshape(local_shards * lanes),
                    logits.reshape(local_shards * lanes, logits.shape[-1]),
                    ncache)

        f = shard_map(local_step, mesh=mesh,
                      in_specs=(P(), cache_specs, tok_spec, P()),
                      out_specs=(tok_spec, tok_spec, cache_specs),
                      check_replication=False)
        return f(params, cache, tokens, pos)

    return sharded_step
