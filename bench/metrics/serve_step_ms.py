"""Layer `serve step` (launch/steps.py serve, and the canary's precise
step): device time of the decode-step programs (`serve_step`,
`sharded_step`) inside the traced window, divided by the engine ticks in
it, in ms. Moves tokens_per_s."""

PROGRAMS = ("serve_step", "sharded_step")


def read(ctx):
    ticks = ctx.trace["ticks"]
    t = sum(ctx.trace["programs"].get(n, {}).get("device_s", 0.0)
            for n in PROGRAMS)
    if not ticks or not t:
        return None
    return t / ticks * 1e3
