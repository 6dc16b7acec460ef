"""mfu (model step): model FLOPs of the tokens the window processed, over
the window's seconds times the device's bf16 peak, in percent.  FLOPs per
token come from the configuration's widths and the keys the token attends
(bench/work.py)."""
import numpy as np

from bench import work


def read(ctx):
    pos = [p for _, ps in ctx.bodies for p in ps]
    if not pos:
        return None
    keys = work.attended(np.asarray(pos), ctx.geo["page_t"],
                         ctx.geo["ring_pages"])
    flops = float(np.sum(work.token_flops(ctx.f, keys)))
    return 100.0 * flops / ((ctx.t1 - ctx.t0) * ctx.peaks["bf16_flops"])
