"""setup_s: process start to the window's first step -- weights, slow
stores, compile or cache load, and the warm-up traffic."""


def read(ctx):
    return ctx.setup_s
