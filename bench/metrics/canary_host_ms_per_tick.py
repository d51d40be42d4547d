"""Layer `qos` (serving/scheduler.py, the `tick.canary` span): device idle
time inside the traced window that falls in the canary, that is, the two
logits copies to the host and the quality monitor's scoring, divided by
the engine ticks in the window, in ms. From the trace's `phases`
(bench/trace/phases.py). Moves tokens_per_s. QoS cells only."""


def read(ctx):
    p = ctx.trace.get("phases", {}).get("tick.canary")
    if ctx.traffic["engine"] != "qos" or not p or not ctx.trace["ticks"]:
        return None
    return p["idle_s"] / ctx.trace["ticks"] * 1e3
