"""Layer `device`: the share of the traced window in which no operation
ran on the device, in % (1 - busy / window, busy averaged over the cell's
chips). Moves tokens_per_s."""


def read(ctx):
    w = ctx.trace["window_s"]
    if not w:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / w)
