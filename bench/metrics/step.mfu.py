"""Model flops of every position the window's iterations processed, at the
served row's ranks (``work/model.py``), over the window's seconds times
the chip's peak bf16 FLOP/s. The whole step's share of the chip's peak:
it bounds what any kernel's roofline share can buy end to end."""
LAYER, UNIT, MOVES = "model step", "%", "tokens_per_s"


def read(ctx):
    its = ctx.items_between(ctx.run.open, ctx.run.close)
    if not its:
        return None
    flops = ctx.work("model").work(its, ctx.model, ctx.per_position_flops)
    seconds = ctx.run.close - ctx.run.open
    return 100.0 * flops / (seconds * ctx.peak["bf16_flops_per_s"])
