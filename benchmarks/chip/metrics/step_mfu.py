"""Model FLOP of one block over the traced block time, the chips and their
bf16 peak, in percent.  Model FLOP: the block's tokens times the
configuration's family's ``flops_per_token`` (no recomputation)."""
from benchmarks.chip import counts


def read(ctx):
    flops = (counts.block_tokens(ctx.traffic)
             * ctx.layout.flops_per_token(ctx.cfg, ctx.traffic["seq"]))
    return 100.0 * flops / (ctx.block_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
