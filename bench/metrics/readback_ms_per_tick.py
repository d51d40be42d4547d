"""Layer `engine` (serving/scheduler.py, the `tick.host_read` span): device
idle time inside the traced window that falls in the per-tick read-back
of the tokens (and, in QoS cells, of the TAF `remaining` leaf), divided by
the engine ticks in the window, in ms. From the trace's `phases`
(bench/trace/phases.py). Moves tokens_per_s."""


def read(ctx):
    p = ctx.trace.get("phases", {}).get("tick.host_read")
    if not p or not ctx.trace["ticks"]:
        return None
    return p["idle_s"] / ctx.trace["ticks"] * 1e3
