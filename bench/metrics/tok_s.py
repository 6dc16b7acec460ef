"""tok_s: tokens processed per second over the window -- every prompt and
output token the window's scheduler steps consumed, over their wall time."""


def read(ctx):
    return sum(s.tokens for s in ctx.steps) / (ctx.t1 - ctx.t0)
