"""Layer `qos` (qos/, the canary path of serving/scheduler.py): the share
of the traced window's ticks that re-ran the step through the precise
model for the quality monitor, in %, from `EngineStats.canary_ticks` and
`ticks`. Moves tokens_per_s. QoS cells only."""


def read(ctx):
    if ctx.traffic["engine"] != "qos" or not ctx.counters["ticks"]:
        return None
    return 100.0 * ctx.counters["canary_ticks"] / ctx.counters["ticks"]
