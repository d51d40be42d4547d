"""Layer `device`: model FLOP/s utilisation of the whole serving step, in
%: the operations of every token processed in the traced window (prompt
tokens of each admitted wave, and each live lane's token per tick, at its
position), from the configuration's shapes (its family's counts,
`bench.cells.family`), over the window's length times the cell's chips
times the chip's peak bf16 rate. Moves tokens_per_s."""

from bench import cells


def read(ctx):
    w = ctx.trace["window_s"]
    if not w or not (ctx.ticks or ctx.admits):
        return None
    fam = cells.family(ctx.conf)
    P = ctx.traffic["prompt_len"]
    flops = sum(fam.prefill_flops(ctx.conf, P, a["requests"])
                for a in ctx.admits)
    flops += sum(fam.decode_step_flops(ctx.conf, t["live"], t["pos"])
                 for t in ctx.ticks)
    return 100.0 * flops / (w * ctx.chips * ctx.peaks["bf16_flops_per_s"])
