"""idle_share (device): 1 - (union of device-op intervals) / traced
window, in percent."""
from bench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / trace.window_s(ctx.trace))
