"""Layer `engine` (serving/scheduler.py tick loop): device idle time inside
the traced window, divided by the engine ticks in it, in ms. Idle time is
the window less the union of the device's operation intervals; it is the
host's share of a tick (admission, canary copies, token read-back,
retirement, QoS bookkeeping, dispatch). Moves tokens_per_s."""


def read(ctx):
    ticks = ctx.trace["ticks"]
    if not ticks:
        return None
    return ctx.trace["idle_s"] / ticks * 1e3
