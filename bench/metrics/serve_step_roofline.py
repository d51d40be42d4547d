"""Layer `serve step`: the decode step's share of its roofline, in %: the
least time of the traced ticks over the device time of `serve_step`.

Least time of a tick is max(operations / peak bf16 rate, bytes / HBM
bandwidth) for its live lanes at their position (its family's counts,
`bench.cells.family`): every weight once, plus the keys and values of
each live lane's filled positions, never the padding up to the cache's
length. Precise cells only: a TAF step skips layers, so the whole model's
work over its time could read above 100%. Moves tokens_per_s."""

from bench import cells


def read(ctx):
    if ctx.traffic["engine"] != "precise" or not ctx.ticks:
        return None
    p = ctx.trace["programs"].get("serve_step")
    if not p or not p["device_s"]:
        return None
    fam = cells.family(ctx.conf)
    least = sum(cells.least_time_s(
        fam.decode_step_flops(ctx.conf, t["live"], t["pos"]),
        fam.decode_step_bytes(ctx.conf, t["live"], t["pos"]), ctx.peaks)
        for t in ctx.ticks)
    return 100.0 * least / p["device_s"]
