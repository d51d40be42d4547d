"""Layer `qos` (decode-time TAF in models/lm.py): the share of layer-steps
in the traced window that reused a memoised layer output instead of
computing it, in %, from `EngineStats.taf_skipped` and `taf_total`. Moves
tokens_per_s. QoS cells only."""


def read(ctx):
    if ctx.traffic["engine"] != "qos" or not ctx.counters["taf_total"]:
        return None
    return 100.0 * ctx.counters["taf_skipped"] / ctx.counters["taf_total"]
