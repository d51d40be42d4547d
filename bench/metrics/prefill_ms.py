"""Layer `prefill step` (launch/steps.py prefill): device time per run of
the jitted `prefill_step` program inside the traced window, in ms, from
the trace's program events. Both admission shapes count: the first wave's
whole-batch prefill and the one-request prefills of later waves. Moves
tokens_per_s."""


def read(ctx):
    p = ctx.trace["programs"].get("prefill_step")
    if not p or not p["runs"]:
        return None
    return p["device_s"] / p["runs"] * 1e3
