"""mfu (model step): model FLOPs of the tokens the window processed, over
the window's seconds times the device's bf16 peak, in percent.  FLOPs per
token come from the architecture module's count (bench/arch/<model_type>.py
``token_flops``) and the keys the token attends (bench/work.py)."""
from bench import work


def read(ctx):
    pos = [p for _, ps in ctx.bodies for p in ps]
    if not pos:
        return None
    flops = work.model_flops(ctx.arch, ctx.f, pos, ctx.geo["page_t"],
                             ctx.geo["ring_pages"])
    return 100.0 * flops / ((ctx.t1 - ctx.t0) * ctx.peaks["bf16_flops"])
