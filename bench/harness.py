"""One run of one cell: set up, measure a window, check, report.

    set-up   weights from the seed on the device, the engine of the traffic
             file (precise, or QoS with the committed policy), its
             warm-up, which compiles (or loads from the cache) every
             program the waves use, and one untimed wave of one token per
             request, so that every timed wave is admitted into a live
             cache as in a steady deployment;
    window   closed waves through `ServingEngine` for `seconds` seconds:
             submit a wave, admit it, read its first tokens, tick until it
             drains; each tick runs in a `bench.tick` span and is stamped
             on the host clock when it returns (the engine reads its tokens
             back every tick, so the stamp follows the device); a traced
             run profiles the window's first `TRACE_SECONDS` only, since
             writing the trace takes about 5 s per second profiled;
    drain    the wave open at the close finishes untimed;
    check    a sample of finished requests, drawn from the seed and holding
             the longest, against the float32 reference, after the
             program's state is freed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import os
import shutil
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import cells, traffic as traffic_mod

TRACE_DIR = ".bench_traces"     # under the checkout, ignored by git
TRACE_SECONDS = 20.0            # the most of a window a traced run profiles


class NoChip(RuntimeError):
    """The devices cannot run this cell."""


class MissingMetric(RuntimeError):
    """A per-layer reader listed for the cell found nothing to read."""


def check_devices(root: str, chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    try:
        return cells.load_peaks(root, devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e


@dataclasses.dataclass
class Window:
    seconds: float
    reqs: List[Dict]                # every request of every wave started
    ticks: List[Dict]               # stamp, live, pos, traced
    admits: List[Dict]              # stamp, requests, traced
    counters: Dict                  # EngineStats deltas over the traced part


def build_engine(root: str, conf: Dict, traffic: Dict, params):
    from repro import qos
    from repro.models import build
    from repro.serving import ServingEngine
    is_qos = traffic["engine"] == "qos"
    model = build(cells.program_config(conf, approx=is_qos))
    qos_engine = None
    if is_qos:
        doc = cells.load_policy_doc(root, conf["name"])
        policy = qos.QosPolicy.load(cells.policy_path(root, conf["name"]))
        qos_engine = qos.QosEngine(
            policy, doc["targets"], **doc["monitor"],
            config=qos.ControllerConfig(**doc["controller"]))
    return ServingEngine(model, params, slots=traffic["slots"],
                         max_len=traffic["max_len"],
                         prompt_len=traffic["prompt_len"], qos=qos_engine,
                         devices=traffic.get("devices"),
                         shards=traffic.get("shards"))


def _counters(stats) -> Dict:
    return {"ticks": stats.ticks, "canary_ticks": stats.canary_ticks,
            "taf_skipped": stats.taf_skipped, "taf_total": stats.taf_total}


def _lane_knob(engine, lane: int) -> float:
    """The TAF threshold the lane ran under on the last tick (0: precise)."""
    if not engine.knob_events:
        return 0.0
    v = engine.knob_events[-1].value
    if isinstance(v, tuple):
        v = v[lane // engine.lanes_per_shard]
    return float(v)


def warm_wave(engine, waves) -> None:
    """Serve the next wave with one new token per request, untimed. An
    engine with no cache prefills a whole wave in one batch, a path only
    its first wave takes; afterwards each request is prefilled alone and
    spliced into the live cache. This wave takes the first path, so the
    window's waves all take the second."""
    from repro.serving import Request
    for r in next(waves):
        engine.submit(Request(uid=r["uid"], prompt=r["prompt"],
                              max_new_tokens=1, qos_class=r["cls"]))
    while engine.tick() or engine.queue:
        pass


def compiled_programs(engine) -> int:
    """How many programs the engine's jitted steps hold compiled."""
    fns = (engine._prefill, engine._serve, engine._lane_write,
           engine._serve_exact)
    return sum(f._cache_size() for f in fns if hasattr(f, "_cache_size"))


def serve_window(engine, waves, seconds: float, trace_dir=None) -> Window:
    import jax
    from repro.serving import Request
    reqs, ticks, admits = [], [], []
    tracing = trace_dir is not None
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = _counters(engine.stats)
    after = None
    t0 = time.perf_counter()
    end = t0 + seconds
    trace_end = t0 + min(seconds, TRACE_SECONDS)
    while time.perf_counter() < end:
        recs = {}
        for r in next(waves):
            engine.submit(Request(uid=r["uid"], prompt=r["prompt"],
                                  max_new_tokens=r["new_tokens"],
                                  qos_class=r["cls"]))
            recs[r["uid"]] = dict(r, tokens=[], stamps=[], knobs=[],
                                  lane=None)
        with jax.profiler.TraceAnnotation("bench.admit"):
            engine._admit()
            first = np.asarray(engine.tokens)
        now = time.perf_counter()
        admits.append({"stamp": now, "requests": len(recs),
                       "traced": tracing})
        for lane, req in enumerate(engine.active):
            if req is not None and req.uid in recs:
                rec = recs[req.uid]
                rec["lane"] = lane
                rec["tokens"].append(int(first[lane]))
                rec["stamps"].append(now)
                rec["knobs"].append(0.0)
        while any(r is not None for r in engine.active):
            live = [(i, r) for i, r in enumerate(engine.active)
                    if r is not None]
            pos = int(min(engine.pos[i] for i, _ in live))
            step = engine.stats.ticks
            with jax.profiler.StepTraceAnnotation("tick", step_num=step):
                with jax.profiler.TraceAnnotation("bench.tick"):
                    engine.tick()
            now = time.perf_counter()
            for i, r in live:
                rec = recs[r.uid]
                rec["tokens"].append(int(r.output[-1]))
                rec["stamps"].append(now)
                rec["knobs"].append(_lane_knob(engine, i))
            ticks.append({"stamp": now, "live": len(live), "pos": pos,
                          "traced": tracing})
            if tracing and now >= trace_end:
                jax.profiler.stop_trace()
                tracing = False
                after = _counters(engine.stats)
        reqs.extend(recs.values())
    if tracing:
        jax.profiler.stop_trace()
    after = after or _counters(engine.stats)
    return Window(seconds=seconds, reqs=reqs, ticks=ticks, admits=admits,
                  counters={k: after[k] - before[k] for k in after})


def end_to_end(win: Window, t0_window: float) -> Dict[str, float]:
    """tokens_per_s and tpot_ms_p95 over the tokens stamped in the window.
    `t0_window` is the window's start on the host clock."""
    end = t0_window + win.seconds
    n_tokens, gaps = 0, []
    for r in win.reqs:
        st = r["stamps"]
        n_tokens += sum(1 for s in st if s <= end)
        gaps += [b - a for a, b in zip(st, st[1:]) if b <= end]
    return {"tokens_per_s": n_tokens / win.seconds,
            "tpot_ms_p95": float(np.percentile(gaps, 95)) * 1e3}


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def draw_sample(reqs: List[Dict], n: int, seed: int) -> List[Dict]:
    """`n` finished requests drawn from the seed: the longest, then one
    request on each of `n - 1` other lanes, so that a fault confined to
    some lanes cannot hide from the sample."""
    done = [r for r in reqs if len(r["tokens"]) == r["new_tokens"] + 1]
    rng = np.random.default_rng([int(seed), 0x5A])
    longest = max(r["new_tokens"] for r in done)
    tops = [r for r in done if r["new_tokens"] == longest]
    pick = [tops[rng.integers(len(tops))]]
    lanes = sorted({r["lane"] for r in done} - {pick[0]["lane"]})
    for lane in rng.permutation(lanes)[:n - 1]:
        on = [r for r in done if r["lane"] == lane]
        pick.append(on[rng.integers(len(on))])
    return pick


def reference_readings(conf: Dict, traffic: Dict, seed: int,
                       sample: List[Dict], control: bool = False) -> Dict:
    """Per served token of the sample: its gap below the float32
    reference's best logit in units of the reference logits' standard
    deviation, and whether it is the reference's argmax. With `control`,
    also, under `control`, the same two readings of the token the fp8
    control puts first at the same positions: the control put in the
    program's place, for `judge`."""
    import jax.numpy as jnp
    ref = cells.family(conf)
    P = traffic["prompt_len"]
    T = P + traffic["output"]["max"]
    rows = np.zeros((len(sample), T), np.int32)
    idx = []
    for j, r in enumerate(sample):
        seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
        rows[j, :len(seq)] = seq
        idx += [(j, P - 1 + t) for t in range(len(r["tokens"]))]
    served = np.concatenate([r["tokens"] for r in sample])
    n = len(served)
    vocab = conf["vocab_size"]
    ok = served < vocab
    # every run reads the same number of positions (the most a sample can
    # serve, in whole chunks), so the reference's programs compile once
    chunk = 256
    most = len(sample) * (traffic["output"]["max"] + 1)
    width = -(-most // chunk) * chunk
    safe = np.zeros(width, np.int64)
    safe[:n] = np.where(ok, served, 0)
    jj, tt = np.zeros(width, np.int64), np.full(width, P - 1)
    jj[:n], tt[:n] = (np.asarray(a) for a in zip(*idx))
    out = {}
    hidden = {}
    for prec in ["float32"] + (["fp8"] if control else []):
        h = ref.final_hidden(conf, seed, rows, prec)
        hidden[prec] = h[jnp.asarray(jj), jnp.asarray(tt)]
        del h
    gap, top, ctl_gap, ctl_top = [], [], [], []
    for s in range(0, width, chunk):
        lg = ref.head_logits(conf, seed, hidden["float32"][s:s + chunk])
        mx = lg.max(-1)
        sd = lg.std(-1)
        at = jnp.take_along_axis(lg, jnp.asarray(safe[s:s + chunk])[:, None],
                                 -1)[:, 0]
        gap.append(np.asarray((mx - at) / sd))
        top.append(np.asarray(jnp.argmax(lg, -1)))
        if control:
            lc = ref.head_logits(conf, seed, hidden["fp8"][s:s + chunk],
                                 "fp8")
            ct = jnp.argmax(lc, -1)
            atc = jnp.take_along_axis(lg, ct[:, None], -1)[:, 0]
            ctl_gap.append(np.asarray((mx - atc) / sd))
            ctl_top.append(np.asarray(ct))
    gap = np.concatenate(gap)[:n].astype(np.float64)
    gap[~ok] = np.inf
    out["gap"] = gap
    top = np.concatenate(top)[:n]
    out["argmax"] = top == served
    out["served"] = served
    if control:
        out["control"] = {
            "gap": np.concatenate(ctl_gap)[:n].astype(np.float64),
            "argmax": np.concatenate(ctl_top)[:n] == top}
    return out


def _precise_mask(sample: List[Dict]) -> np.ndarray:
    """Per served token: True until the request first ran under an
    approximate TAF threshold (0 means precise)."""
    return np.concatenate([np.cumsum(np.asarray(r["knobs"]) > 0) == 0
                           for r in sample])


def judge(sample: List[Dict], readings: Dict, limits: Dict,
          targets: Optional[Dict], failed: int) -> Dict:
    """The numbers compared, each with its limit. Tokens served before the
    request ran under an approximate TAF threshold are held to the
    precise limit; tokens from then on to their class's mismatch target
    (QoS cells only)."""
    precise = _precise_mask(sample)
    exposed = ~precise
    classes = np.concatenate([[r["cls"]] * len(r["tokens"])
                              for r in sample])
    checks = {"failed_requests": {"value": failed, "limit": 0}}
    gmax = float(readings["gap"][precise].max()) if precise.any() \
        else float("inf")
    checks["max_gap_std"] = {"value": gmax,
                             "limit": limits["max_gap_std"]["limit"],
                             "tokens": int(precise.sum())}
    if targets is not None:
        for cls in sorted(targets):
            m = exposed & (classes == cls)
            rate = float((~readings["argmax"][m]).mean()) if m.any() else 0.0
            checks[f"mismatch_{cls}"] = {"value": rate,
                                         "limit": targets[cls],
                                         "tokens": int(m.sum())}
    return checks


def is_correct(checks: Dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


# --------------------------------------------------------------------------
# per-layer metrics
# --------------------------------------------------------------------------

@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader may read: the trace reduction, the engine's
    counters over the traced part of the window, the traced ticks and
    admissions, and the cell's files."""
    trace: Dict
    counters: Dict
    ticks: List[Dict]
    admits: List[Dict]
    conf: Dict
    traffic: Dict
    peaks: Dict
    chips: int


def load_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: Dict, workload: str, kind: str) -> List[Dict]:
    """The cell's metrics of `kind` (`end_to_end` or `per_layer`)."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def per_layer(root: str, bench: Dict, workload: str,
              ctx: MetricContext) -> Dict:
    out = {}
    for m in cell_metrics(bench, workload, "per_layer"):
        # `<quantity>.<suffix>` is read by `bench/metrics/<quantity>.py`
        value = load_reader(root, m["name"].split(".")[0]).read(ctx)
        if value is None:
            raise MissingMetric(
                f"per-layer metric {m['name']!r} is listed for {workload} "
                f"but its reader found nothing to read in the trace")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# a run
# --------------------------------------------------------------------------

def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, require_chip: bool = True,
             engine_hook: Optional[Callable] = None,
             control: bool = False,
             log: Callable = print) -> Dict:
    """One run; returns the result line's object. `require_chip=False`
    and `engine_hook` (which may break the engine) serve the tests;
    `control` also judges the fp8 control's tokens at the same positions
    by the same checks and limits, under `control` (for setting limits,
    bench/tools/readings.py, and for the control's test)."""
    import jax
    bench = cells.load_benchmark(root)
    cell = cells.find_workload(bench, workload)
    conf = cells.load_config(root, cell["config"])
    traffic = cells.load_traffic(root, cell["traffic"])
    limits = cells.load_limits(root, workload)
    if require_chip:
        peaks = check_devices(root, cell["chips"])
    else:
        peaks = cells.load_peaks(root, "TPU v5 lite")
    devs = jax.devices()

    cfg = cells.program_config(conf, approx=False)
    params = cells.family(conf).program_params(seed, conf,
                                               cfg.padded_vocab_size)
    engine = build_engine(root, conf, traffic, params)
    engine.warmup()
    waves = traffic_mod.waves(traffic, seed, conf["vocab_size"])
    warm_wave(engine, waves)
    if engine_hook is not None:
        engine_hook(engine)
    n_compiled = compiled_programs(engine)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(root, TRACE_DIR,
                                 f"{workload}.{seed}.{os.getpid()}")
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    win = serve_window(engine, waves, seconds, trace_dir)
    log(f"window, drain and trace write: {time.perf_counter() - t0:.1f} s")
    e2e = end_to_end(win, t0)
    log(f"programs compiled in the window: "
        f"{compiled_programs(engine) - n_compiled}")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    targets = None
    if engine.qos is not None:
        targets = cells.load_policy_doc(root, conf["name"])["targets"]
    del engine, params
    gc.collect()

    failed = sum(1 for r in win.reqs
                 if len(r["tokens"]) != r["new_tokens"] + 1)
    sample = draw_sample(win.reqs, traffic["sample_requests"], seed)
    t_ref = time.perf_counter()
    readings = reference_readings(conf, traffic, seed, sample, control)
    checks = judge(sample, readings, limits, targets, failed)
    log(f"reference: {len(sample)} requests, {len(readings['gap'])} served "
        f"tokens, {time.perf_counter() - t_ref:.1f} s")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": is_correct(checks), "attempted": len(win.reqs),
              "failed": failed}
    if trace:
        from bench.trace import xplane
        t_red = time.perf_counter()
        red = xplane.reduce_trace(trace_dir)
        log(f"trace reduced in {time.perf_counter() - t_red:.1f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = MetricContext(
            trace=red, counters=win.counters,
            ticks=[t for t in win.ticks if t["traced"]],
            admits=[a for a in win.admits if a["traced"]],
            conf=conf, traffic=traffic, peaks=peaks, chips=cell["chips"])
        result["metrics"] = per_layer(root, bench, workload, ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["device"] = device
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in red["device_ops"]],
            "idle_gaps": [[n, s] for n, s in red["idle_gaps"]]}
    else:
        # the name up to its first `.` names the quantity, so one quantity
        # may have a bound of its own in some cells (`tpot_ms_p95.qos`)
        values = dict(e2e, setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"].split(".")[0]],
                        "unit": m["unit"]}
            for m in cell_metrics(bench, workload, "end_to_end")}
        result["device"] = device
    if control:
        ctl = judge(sample, readings["control"], limits, targets, 0)
        result["control"] = {"correct": is_correct(ctl), "checks": ctl}
    result["checks"] = checks
    return result
