"""Layer `serve step` (launch/steps.py serve under decode TAF): device time
of the decode-step programs (`serve_step`, `sharded_step`) dispatched in
the `tick.serve` span, divided by the engine ticks in the traced window,
in ms: `serve_step_ms` without the canary's exact step. From the trace's
`phases` (bench/trace/phases.py). Moves tokens_per_s. QoS cells only."""

from bench.trace import phases


def read(ctx):
    p = ctx.trace.get("phases", {}).get("tick.serve")
    if ctx.traffic["engine"] != "qos" or not p or not ctx.trace["ticks"]:
        return None
    t = phases.step_device_s(p)
    return t / ctx.trace["ticks"] * 1e3 if t else None
