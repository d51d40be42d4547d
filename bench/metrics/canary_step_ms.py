"""Layer `qos` (the canary's precise step, launch/steps.py serve): device
time of the decode-step programs (`serve_step`, `sharded_step`)
dispatched in the `tick.canary` span, divided by the canary spans in the
traced window, in ms. From the trace's `phases` (bench/trace/phases.py).
Moves tokens_per_s. QoS cells only."""

from bench.trace import phases


def read(ctx):
    p = ctx.trace.get("phases", {}).get("tick.canary")
    if ctx.traffic["engine"] != "qos" or not p:
        return None
    t = phases.step_device_s(p)
    return t / p["count"] * 1e3 if t else None
