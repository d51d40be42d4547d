"""Layer `qos` (serving/scheduler.py, the `tick.actuate` and
`tick.qos_update` spans): device idle time inside the traced window that
falls in the controllers' plan and knob write, and in their update after
the tick, divided by the engine ticks in the window, in ms. From the
trace's `phases` (bench/trace/phases.py). Moves tokens_per_s. QoS cells
only."""

SPANS = ("tick.actuate", "tick.qos_update")


def read(ctx):
    phases = ctx.trace.get("phases", {})
    if (ctx.traffic["engine"] != "qos" or not ctx.trace["ticks"]
            or any(s not in phases for s in SPANS)):
        return None
    return sum(phases[s]["idle_s"] for s in SPANS) / ctx.trace["ticks"] * 1e3
