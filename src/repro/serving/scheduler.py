"""Continuous-batching serving loop.

Production serving substrate: a slot-based scheduler multiplexes many
requests over one decode-step function. Requests enter a FIFO queue; free
slots are (re)filled via per-slot prefill; every engine tick decodes ONE
token for ALL active slots (the batched serve_step that decode_32k lowers);
finished sequences (EOS or max_tokens) free their slot immediately --
no head-of-line blocking on long generations.

Composes with the paper's technique: a TAF `approx_decode` config skips
stable layers inside the shared decode step, and the engine reports the
skipped-layer fraction alongside throughput.

QoS hook (docs/qos.md): pass `qos=QosEngine(...)` and the decode loop runs
under a controller-chosen spec. Each tick the engine groups live lanes by
their request class's current knob (`batching.group_lanes` via
`QosEngine.plan_tick`), actuates the strictest live rung by writing the
TAF threshold into the decode cache -- a TRACED value, so knob moves never
recompile -- and, on canary ticks, runs the step through the precise model
from the same pre-tick state, before the serve step consumes it, and feeds
the compared QoI to the quality monitor: the two steps' token ids where
it scores token ids ("mcr"), which copies only the exact step's tokens to
the host, else both steps' logits. A hard fallback zeroes both
the threshold and the in-flight prediction counters, so "precise" takes
effect on the very next token.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.types import ApproxSpec, Technique
from repro.launch import steps as steps_mod
from repro.models import lm as lm_mod
from repro.models.lm import Model
from repro.obs import recorder as obs_recorder
from repro.obs import trace
from repro.obs.metrics import percentile as _percentile


def _without_cache(step):
    """`step` with its cache output dropped: (next_tokens, logits). The
    jitted program keeps the step's name, and builds no new cache."""
    @functools.wraps(step)
    def tokens_and_logits(params, cache, tokens, pos):
        return step(params, cache, tokens, pos)[:2]

    return tokens_and_logits


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (prompt_len,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    qos_class: str = "default"      # maps to a QosEngine target class
    # filled by the engine:
    output: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class KnobMove:
    """One actuator write: the typed record behind `knob_log`.

    `value`/`previous` are the threshold actually written -- a float, or
    a per-shard tuple on sharded engines (`previous` is None for the
    first actuation). `reason` classifies the move from the controller
    state and the value delta: init | tighten | loosen | fallback |
    mixed. Emitted as an obs `knob_move` event when tracing."""
    tick: int
    value: object
    previous: object
    reason: str
    shard: Optional[int] = None


@dataclasses.dataclass
class EngineStats:
    ticks: int = 0
    tokens_out: int = 0
    finished: int = 0
    taf_skipped: int = 0
    taf_total: int = 0
    canary_ticks: int = 0           # ticks re-executed through the oracle
    canary_host_bytes: int = 0      # bytes the canary ticks copied to host
    knob_moves: int = 0             # actuator writes (QoS rung changes)
    # per-request latency samples (seconds), appended as requests progress:
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    latency_s: List[float] = dataclasses.field(default_factory=list)
    # the expert row counts of a dropless expert stack, (routed per layer
    # and held expert, computed) or None (`models.lm.moe_row_counts`); the
    # moe_rows_* properties call it, so a tick never reads the device
    moe_row_counts: Optional[Callable[[], Optional[Tuple]]] = \
        dataclasses.field(default=None, repr=False, compare=False)

    def _moe_rows(self) -> Optional[Tuple]:
        return self.moe_row_counts() if self.moe_row_counts else None

    @property
    def moe_rows_routed(self) -> int:
        """Rows routed to the held experts, every expert layer, every
        decode step since the live cache was made."""
        rows = self._moe_rows()
        return int(rows[0].sum()) if rows else 0

    @property
    def moe_rows_computed(self) -> int:
        """Expert rows the same decode steps' products computed (a masked
        product computes every held expert on every lane)."""
        rows = self._moe_rows()
        return rows[1] if rows else 0

    @property
    def moe_rows_max(self) -> int:
        """The largest count of rows routed to one held expert of one
        layer."""
        rows = self._moe_rows()
        return int(rows[0].max()) if rows else 0

    @property
    def taf_skip_fraction(self) -> float:
        return self.taf_skipped / max(self.taf_total, 1)

    @property
    def ttft_p50(self) -> Optional[float]:
        return _percentile(self.ttft_s, 50)

    @property
    def ttft_p99(self) -> Optional[float]:
        return _percentile(self.ttft_s, 99)

    @property
    def latency_p50(self) -> Optional[float]:
        return _percentile(self.latency_s, 50)

    @property
    def latency_p99(self) -> Optional[float]:
        return _percentile(self.latency_s, 99)

    def latency_summary(self) -> Dict[str, Optional[float]]:
        """Time-to-first-token and end-to-end request latency, p50/p99 --
        what the QoS benchmark reports alongside throughput and error."""
        return {
            "ttft_p50_s": self.ttft_p50, "ttft_p99_s": self.ttft_p99,
            "latency_p50_s": self.latency_p50,
            "latency_p99_s": self.latency_p99,
            "requests": len(self.latency_s),
        }


class ServingEngine:
    """Slot-based continuous batching over a fixed decode batch size.

    Sharded mode (`mesh=`/`devices=`): the decode step runs
    `shard_map`'d over the mesh's data axes (`make_sharded_serve_step`)
    with `shards` logical shards of `slots // shards` contiguous lanes
    each. Logical shards are decoupled from the device count -- any
    multiple of the mesh's data extent -- so the same engine config runs
    1-device and 8-device with bit-identical outputs. Each shard carries
    its own TAF detector state and traced threshold knob; with `qos=`,
    the control plane is switched to per-shard actuation
    (`QosEngine.enable_sharding`) and every tick plans, canaries, and
    updates per shard.
    """

    def __init__(self, model: Model, params, *, slots: int = 4,
                 max_len: int = 256, prompt_len: int = 32, qos=None,
                 mesh=None, devices: Optional[int] = None,
                 shards: Optional[int] = None, lint: bool = False):
        self.model = model
        self.params = params
        self.n_slots = slots
        self.max_len = max_len
        self.prompt_len = prompt_len
        self.queue: Deque[Request] = collections.deque()
        self.active: List[Optional[Request]] = [None] * slots
        self.pos = np.zeros(slots, np.int64)       # next write position
        self.limit = np.zeros(slots, np.int64)     # stop position
        self.stats = EngineStats(moe_row_counts=self._moe_row_counts)
        if devices is not None and mesh is None:
            from repro.runtime import elastic
            if devices > len(jax.devices()):
                raise ValueError(
                    f"devices={devices} but only {len(jax.devices())} "
                    f"visible (set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N for a fake "
                    f"multi-device host)")
            mesh = elastic.data_mesh_for(devices)
        self.mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.runtime import sharding as shardlib
            # Commit the params to the mesh ONCE (replicated). Feeding the
            # sharded step uncommitted single-device arrays makes pjit
            # re-replicate every leaf on EVERY call -- per-tick
            # batched_device_put was the whole serving budget (~5ms/tick on
            # the 8-device CI host) before this landed.
            self.params = jax.device_put(
                params, NamedSharding(mesh, PartitionSpec()))
            da = shardlib.data_axes(mesh)
            n_data = 1
            for a in da:
                n_data *= int(mesh.shape[a])
            self.n_shards = int(shards) if shards is not None else n_data
            if self.n_shards < 1 or self.n_shards % n_data:
                raise ValueError(
                    f"shards ({self.n_shards}) must be a positive multiple "
                    f"of the mesh's data extent ({n_data})")
            if slots % self.n_shards:
                raise ValueError(
                    f"slots ({slots}) must divide evenly into "
                    f"{self.n_shards} shards")
        else:
            if shards not in (None, 1):
                raise ValueError(
                    "shards needs a mesh (pass devices=1 for a "
                    "single-device data-parallel mesh)")
            self.n_shards = 1
        self.lanes_per_shard = slots // self.n_shards
        # one shared cache sized (slots, max_len); per-slot prefill writes
        # into its row via the batched prefill below. Prefill stays a
        # plain jit even in sharded mode: admission cost is per-REQUEST
        # (not per-token) and its cache output is resharded once.
        self._prefill = jax.jit(steps_mod.make_prefill_step(model, max_len))
        self._lane_write = self._make_lane_write()
        # the step consumes the live cache: each tick writes its rows into
        # the donated buffer in place, and the engine keeps only the cache
        # the step returns
        if mesh is not None:
            self._serve = jax.jit(steps_mod.make_sharded_serve_step(
                model, mesh, self.n_shards, slots), donate_argnums=(1,))
        else:
            self._serve = jax.jit(steps_mod.make_serve_step(model),
                                  donate_argnums=(1,))
        self.cache = None
        self._cache_steps = 0       # decode steps the live cache has had
        self.tokens = self._place_tokens(jnp.zeros((slots,), jnp.int32))
        self.qos = qos
        self._knob = None                    # last actuated threshold(s)
        # typed engine-level knob trajectory (controller trajectories
        # live on the QosEngine); the legacy `knob_log` view derives
        # from it. Sharded engines log a per-shard tuple per move.
        self.knob_events: List[KnobMove] = []
        self._serve_exact = None
        if qos is not None:
            if (model.cfg.approx_decode.technique != Technique.TAF
                    or model.cfg.use_mla or model.cfg.moe is not None):
                raise ValueError(
                    "QoS-controlled serving needs decode-time TAF: build "
                    "the model with cfg.approx_decode = a TAF spec (the "
                    "threshold is the online actuator)")
            # The actuator writes ONLY the threshold scalar, so every
            # rung must describe THIS model's decode step (the ladder
            # semantics live qos-side; see the helper's docstring).
            from repro.qos import validate_ladder_taf
            validate_ladder_taf(qos.policy, model.cfg.approx_decode.taf)
            # the canary oracle: the SAME params through a precise decode
            # step (approx_decode disabled). Its cache layout matches --
            # the extra 'taf' entry rides through the pytree untouched.
            # In sharded mode the oracle goes through the SAME sharded
            # wrapper, so its lane->device packing (and therefore its
            # numerics) match the approximate step bit for bit. It returns
            # only (next_tokens, logits): it reads the pre-tick cache,
            # which the approximate step consumes after it, and builds no
            # cache of its own.
            from repro.models import build
            exact_model = build(dataclasses.replace(
                model.cfg, approx_decode=ApproxSpec()))
            if mesh is not None:
                self._serve_exact = jax.jit(_without_cache(
                    steps_mod.make_sharded_serve_step(
                        exact_model, mesh, self.n_shards, slots)))
                qos.enable_sharding(self.n_shards)
            else:
                self._serve_exact = jax.jit(_without_cache(
                    steps_mod.make_serve_step(exact_model)))
        if lint:
            # opt-in approxlint pass over what this engine will actually
            # serve: the policy ladder (A004, raw entries, cross-checked
            # against THIS model's structural TAF params) and the mesh
            # commitment of every leaf already placed (A005 -- the params;
            # the cache is audited too once prefilled, but the params are
            # where the PR 6 per-tick re-shard regression lived)
            from repro.analysis import rules as lint_rules
            findings = []
            if qos is not None:
                t = model.cfg.approx_decode.taf
                findings += lint_rules.check_policy_document(
                    qos.policy.to_json(), subject="engine.policy",
                    model_taf=(t.history_size, t.prediction_size))
            findings += lint_rules.check_engine_placement(self)
            if findings:
                raise ValueError(
                    "approxlint found serving misconfigurations: "
                    + "; ".join(f"{f.rule} {f.subject}: {f.message}"
                                for f in findings))

    def _moe_row_counts(self) -> Optional[Tuple]:
        """The live cache's expert row counts, read off the device."""
        return lm_mod.moe_row_counts(self.model.cfg, self.cache,
                                     self._cache_steps, self.lanes_per_shard)

    @property
    def knob_log(self) -> List[tuple]:
        """Backward-compatible `(tick, value)` view of `knob_events` --
        exactly the tuples the pre-obs list held, so `BENCH_qos.json`
        trajectories and the sharded-parity tests compare unchanged."""
        return [(m.tick, m.value) for m in self.knob_events]

    def _knob_reason(self, val, prev) -> str:
        """Classify an actuator write from controller state + the value
        delta. The plan's knob realizes decisions the controllers took at
        the END of the previous tick, so `in_fallback` is current here."""
        if prev is None:
            return "init"
        if self.qos is not None and any(
                c.in_fallback for c in self.qos.controllers.values()):
            return "fallback"
        old = prev if isinstance(prev, tuple) else (prev,)
        new = val if isinstance(val, tuple) else (val,)
        if len(old) != len(new):            # resharding edge: no delta
            return "init"
        up = any(n > o for o, n in zip(old, new))
        down = any(n < o for o, n in zip(old, new))
        if up and down:
            return "mixed"
        # lower TAF threshold => fewer skips => more precise
        return "tighten" if down else "loosen"

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def mesh_shape(self) -> Optional[tuple]:
        if self.mesh is None:
            return None
        return tuple(int(self.mesh.shape[a]) for a in self.mesh.axis_names)

    def _lane_shard(self, lane: int) -> int:
        """Shards are contiguous lane ranges: lane -> owning shard."""
        return lane // self.lanes_per_shard

    @property
    def _admit_width(self) -> int:
        """Admission batch width: how many arriving requests one prefill +
        one cache splice covers. Lanes-per-shard, capped BELOW the full
        batch -- the splice tells batch rows from batchless detector
        state by their differing batch extents, so the width must not
        equal the slot count."""
        return (self.lanes_per_shard
                if self.lanes_per_shard < self.n_slots else 1)

    def _make_lane_write(self):
        """Jitted multi-lane cache surgery: splice a batch-W prefill's
        rows into the live cache at traced `lanes` (one compile covers
        every slot combination). Leaves without a batch dim (per-shard
        detector state, knob thresholds) keep their LIVE values:
        admission must not reset another lane's quality state or the
        actuated knob. This is what makes admission cost per-REQUEST
        instead of per-batch -- the full-batch re-prefill it replaced
        was ~a whole decode tick of compute per arriving request, threw
        away every ongoing lane's generated KV, and (on a mesh) stalled
        every tick of the arrival phase on eager multi-device gathers."""
        n = self.n_slots

        def write(cache, rows, tokens, row_logits, lanes):
            w = lanes.shape[0]

            def one(c, r):
                if c.ndim != r.ndim:
                    return c        # sharded detector state: per-shard
                axis = None
                for ax, (cs, rs) in enumerate(zip(c.shape, r.shape)):
                    if cs != rs:
                        if rs == w and cs == n:
                            axis = ax
                            break
                        return c    # non-batch mismatch: keep live state
                if axis is None:
                    return c        # batchless leaf (detector state)
                for j in range(w):  # w is small and static: unrolled
                    row = jax.lax.dynamic_index_in_dim(r, j, axis,
                                                       keepdims=True)
                    c = jax.lax.dynamic_update_slice_in_dim(
                        c, row.astype(c.dtype), lanes[j], axis)
                return c

            new_cache = jax.tree_util.tree_map(one, cache, rows)
            new_toks = jnp.argmax(row_logits, axis=-1).astype(tokens.dtype)
            # duplicate lanes (padding repeats row 0) carry identical
            # values, so scatter order cannot matter
            new_tokens = tokens.at[lanes].set(new_toks)
            return new_cache, new_tokens

        return jax.jit(write)

    def _place_cache(self, cache):
        """Commit every cache leaf to its canonical mesh sharding
        (`decode_partition_specs`): batch leaves over the data axis,
        detector state over its shard dim, the rest replicated. Leaves
        already resident under the right sharding pass through untouched,
        so this is cheap to call after any host-side cache surgery
        (admission prefill, knob writes) -- and calling it is what keeps
        the jitted sharded step at ONE sharding signature: mixed
        committed/uncommitted inputs would both recompile per combination
        and re-shard every leaf on every tick."""
        if self.mesh is None or cache is None:
            return cache
        from jax.sharding import NamedSharding
        from repro.runtime import sharding as shardlib
        specs = shardlib.decode_partition_specs(self.mesh, cache,
                                                self.n_slots)
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(self.mesh, spec)), cache, specs)

    def _place_tokens(self, tokens):
        if self.mesh is None:
            return tokens
        from jax.sharding import NamedSharding
        from repro.runtime import sharding as shardlib
        return jax.device_put(
            tokens, NamedSharding(self.mesh, shardlib.batch_spec(self.mesh)))

    def _shard_cache(self, cache):
        """Convert a freshly prefilled cache to the sharded layout (leading
        shard dim on the TAF detector state and the expert row counter)
        and commit it to the mesh. No-op unsharded."""
        if self.mesh is None or cache is None:
            return cache
        return self._place_cache(lm_mod.shard_decode_state(cache,
                                                           self.n_shards))

    def warmup(self):
        """Compile prefill, serve, and (QoS) the canary oracle on
        throwaway state, so the first timed tick measures decode, not
        compilation. Benchmarks call this outside their timed region --
        the PR 5 review caught single-device compile time polluting
        throughput, and the sharded step compiles are bigger still.
        Engine state is untouched."""
        with trace.span("engine.warmup", slots=self.n_slots,
                        shards=self.n_shards):
            self._warmup_body()

    def _warmup_body(self):
        prompts = jnp.zeros((self.n_slots, self.prompt_len), jnp.int32)
        logits, cache = self._prefill(self.params, {"tokens": prompts})
        cache = self._shard_cache(cache)
        tokens = self._place_tokens(
            jnp.argmax(logits, axis=-1).astype(jnp.int32))
        pos = jnp.int32(self.prompt_len)
        # in a tick's order: the canary reads the cache before the serve
        # step consumes it, and the splice takes the cache the step returns
        if self._serve_exact is not None:
            jax.block_until_ready(
                self._serve_exact(self.params, cache, tokens, pos)[0])
        _, _, cache = self._serve(self.params, cache, tokens, pos)
        jax.block_until_ready(cache)
        if self.n_slots > 1:
            # the admission path: batch-W prefill + multi-lane splice
            w = self._admit_width
            row_logits, rows = self._prefill(
                self.params,
                {"tokens": jnp.zeros((w, self.prompt_len), jnp.int32)})
            jax.block_until_ready(self._lane_write(
                cache, rows, tokens, row_logits,
                jnp.zeros((w,), jnp.int32))[1])

    def submit(self, req: Request):
        req.submitted_at = time.time()
        self.queue.append(req)

    def _admit(self):
        """Fill free slots from the queue. The FIRST admission prefills
        the whole batch (there is no live cache yet); afterwards each
        arriving request costs one batch-1 prefill plus a per-lane cache
        splice (`_make_lane_write`), so admission is per-request work that
        leaves ongoing lanes' KV, detector state, and the actuated knob
        untouched -- a production multi-host engine admits the same way.
        An admission runs in the `engine.admit` span."""
        free = [i for i, r in enumerate(self.active) if r is None]
        if free and self.queue:
            with trace.span("engine.admit"):
                self._fill(free)

    def _fill(self, free: List[int]):
        admitted = []
        for i in free:
            if not self.queue:
                break
            req = self.queue.popleft()
            self.active[i] = req
            self.pos[i] = self.prompt_len
            self.limit[i] = min(self.prompt_len + req.max_new_tokens,
                                self.max_len)
            admitted.append(i)
        # batch-1 surgery cannot tell a 1-slot batch dim from batchless
        # detector state, so 1-slot engines always take the full path
        if self.cache is None or self.n_slots == 1:
            prompts = np.zeros((self.n_slots, self.prompt_len), np.int32)
            for i, r in enumerate(self.active):
                if r is not None:
                    p = r.prompt[-self.prompt_len:]
                    prompts[i, -len(p):] = p
            logits, cache = self._prefill(self.params,
                                          {"tokens": jnp.asarray(prompts)})
            self.cache = self._shard_cache(cache)
            self._cache_steps = 0
            self.tokens = self._place_tokens(
                jnp.argmax(logits, axis=-1).astype(jnp.int32))
            self._knob = None   # fresh cache: actuate on the next plan
            return
        cache, tokens = self.cache, self.tokens
        w = self._admit_width
        for g in range(0, len(admitted), w):
            grp = admitted[g:g + w]
            prompts = np.zeros((w, self.prompt_len), np.int32)
            lanes = np.zeros((w,), np.int32)
            for j, i in enumerate(grp):
                p = self.active[i].prompt[-self.prompt_len:]
                prompts[j, -len(p):] = p
                lanes[j] = i
            # pad short groups by re-writing row 0 (idempotent)
            for j in range(len(grp), w):
                prompts[j] = prompts[0]
                lanes[j] = lanes[0]
            row_logits, rows = self._prefill(self.params,
                                             {"tokens": jnp.asarray(prompts)})
            cache, tokens = self._lane_write(cache, rows, tokens,
                                             row_logits,
                                             jnp.asarray(lanes))
        self.cache = self._place_cache(cache)
        self.tokens = self._place_tokens(tokens)

    def _apply_knob(self, knob):
        """Write the controller-chosen TAF threshold(s) into the decode
        cache.

        The threshold is a traced input of the jitted serve step, so this
        is a pure data write -- no recompilation. `None` (precise) writes
        0.0 AND cancels in-flight predictions ("remaining"), making a hard
        fallback effective on the next token rather than after up to
        prediction_size more approximated layer-steps. Sharded engines
        pass a per-shard sequence (`TickPlan.shard_knobs`): each value
        lands on its shard's row of the threshold leaf, and only shards
        set precise have their predictions cancelled.
        """
        if isinstance(knob, (list, tuple)):
            val = tuple(0.0 if k is None else float(k) for k in knob)
        else:
            val = 0.0 if knob is None else float(knob)
        if self.cache is None or val == self._knob:
            return
        from repro.qos import set_decode_threshold
        # re-commit after the write: the threshold/remaining leaves come
        # out of host-dispatched jnp ops with default placement, and an
        # uncommitted leaf in the serve inputs costs a recompile plus a
        # per-tick re-shard of the whole cache
        self.cache = self._place_cache(set_decode_threshold(self.cache,
                                                            val))
        prev = self._knob
        self._knob = val
        # Admission re-prefills rebuild the cache and force a re-apply of
        # the SAME value (self._knob reset to None); that is maintenance,
        # not a controller decision -- only genuine value changes are
        # knob moves in the stats and the trajectory artifact.
        if not self.knob_events or self.knob_events[-1].value != val:
            self.stats.knob_moves += 1
            last = (self.knob_events[-1].value if self.knob_events
                    else prev)
            move = KnobMove(tick=self.stats.ticks, value=val,
                            previous=last,
                            reason=self._knob_reason(val, last))
            self.knob_events.append(move)
            trace.event("knob_move", tick=move.tick, value=move.value,
                        previous=move.previous, reason=move.reason)

    def _canary_host_bytes(self) -> int:
        """Bytes one canary tick copies to the host: the exact step's
        (slots,) int32 tokens where the monitor scores token ids, else the
        exact and the approximate step's (slots, vocab) float32 logits."""
        if self.qos.scores_tokens:
            return self.n_slots * 4
        return 2 * self.n_slots * self.model.cfg.padded_vocab_size * 4

    def _score_canary(self, exact, approx, live, lane_classes,
                      shard_classes):
        """Feed one canary tick's exact and approximate QoI of the live
        lanes (host arrays, one row per lane) to the quality monitor."""
        if self.sharded:
            # per-shard attribution: each shard's slice is scored
            # separately, so a canary error is credited only to the shard
            # (and the classes) that ran under that knob
            for s in range(self.n_shards):
                lanes = [i for i in live if self._lane_shard(i) == s]
                if lanes:
                    self.qos.observe_qoi(exact[lanes], approx[lanes],
                                         shard_classes[s], shard=s)
        else:
            self.qos.observe_qoi(exact[live], approx[live], lane_classes)

    def tick(self) -> int:
        """One engine step: admit, decode one token for all active slots,
        retire finished requests. Returns number of live slots.

        Instrumentation contract (docs/observability.md): the obs hooks
        below are profiler annotations, host-side timers and event
        appends only -- they must never add a `block_until_ready`, read
        a traced value, or perturb the serve signature. Zero extra
        compiles with obs on OR off is pinned by `tests/test_obs.py` via
        `_serve._cache_size()`, and the disabled-path cost by the
        BENCH_obs throughput-ratio gate."""
        rec = obs_recorder.get_recorder()
        t_tick = time.perf_counter() if rec is not None else 0.0
        with trace.span("engine.tick", tick=self.stats.ticks):
            self._admit()
            live = [i for i, r in enumerate(self.active) if r is not None]
            if not live:
                return 0
            lane_classes = []
            shard_classes = None
            if self.qos is not None:
                lane_classes = [self.active[i].qos_class for i in live]
                with trace.span("tick.actuate"):
                    if self.sharded:
                        shard_classes = [[] for _ in range(self.n_shards)]
                        for i in live:
                            shard_classes[self._lane_shard(i)].append(
                                self.active[i].qos_class)
                        plan = self.qos.plan_shards(shard_classes)
                        self._apply_knob(plan.shard_knobs)
                    else:
                        plan = self.qos.plan_tick(lane_classes)
                        self._apply_knob(plan.knob)
            pos = int(self.pos[live].min())  # single shared timeline pos
            canary = self.qos is not None and self.qos.should_sample()
            with contextlib.ExitStack() as in_canary:
                if canary:
                    # canary: the precise oracle from the SAME pre-tick
                    # state, dispatched before the serve step consumes it;
                    # the serve step's and the read-back's spans nest
                    # inside this one
                    host_bytes = self._canary_host_bytes()
                    in_canary.enter_context(
                        trace.span("tick.canary", host_bytes=host_bytes))
                    exact_tokens, exact_logits = self._serve_exact(
                        self.params, self.cache, self.tokens, jnp.int32(pos))
                with trace.span("tick.serve", live=len(live)):
                    self.tokens, logits, self.cache = self._serve(
                        self.params, self.cache, self.tokens, jnp.int32(pos))
                    self._cache_steps += 1
                with trace.span("tick.host_read"):
                    toks = np.asarray(self.tokens)
                    if self.cache is not None and "taf" in self.cache:
                        rem = np.asarray(self.cache["taf"]["remaining"])
                        self.stats.taf_skipped += int((rem > 0).sum())
                        self.stats.taf_total += rem.size
                if canary:
                    # Score ONLY the live lanes -- idle/retired slots hold
                    # zero-padded or stale state nobody consumes, and
                    # their garbage outputs would pollute the estimate.
                    if self.qos.scores_tokens:
                        # the QoI is the token ids: the approximate ones
                        # are the tokens just read back, so only the exact
                        # step's are copied
                        qoi = np.asarray(exact_tokens), toks
                    else:
                        qoi = self.qos.qoi(exact_logits, logits)
                    self._score_canary(*qoi, live, lane_classes,
                                       shard_classes)
                    self.stats.canary_ticks += 1
                    self.stats.canary_host_bytes += host_bytes
            now = time.time()
            with trace.span("tick.retire"):
                for i in live:
                    req = self.active[i]
                    if req.first_token_at is None:
                        req.first_token_at = now
                        self.stats.ttft_s.append(now - req.submitted_at)
                    req.output.append(int(toks[i]))
                    self.pos[i] += 1
                    self.stats.tokens_out += 1
                    done = (self.pos[i] >= self.limit[i] or
                            (req.eos_id is not None
                             and toks[i] == req.eos_id))
                    if done:
                        req.finished_at = now
                        self.stats.latency_s.append(now - req.submitted_at)
                        self.active[i] = None
                        self.stats.finished += 1
            self.stats.ticks += 1
            if self.qos is not None:
                with trace.span("tick.qos_update"):
                    if self.sharded:
                        self.qos.update_shards(shard_classes)
                    else:
                        self.qos.update(lane_classes)
        if rec is not None:
            # close out the note the QoS update opened for this tick
            rec.amend(tick_s=time.perf_counter() - t_tick, live=len(live),
                      knob=self._knob)
        return len([r for r in self.active if r is not None])

    def run_until_drained(self, max_ticks: int = 10_000) -> EngineStats:
        for _ in range(max_ticks):
            live = self.tick()
            if live == 0 and not self.queue:
                break
        return self.stats
