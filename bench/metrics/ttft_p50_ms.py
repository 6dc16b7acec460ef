"""ttft_p50_ms: median time from a client's send to its request's first
token, over the requests sent in the window.  The harness steps on after
the window until each of them has its first token (DRAIN), so requests
sent late in the window count too."""
import numpy as np

DRAIN = True


def read(ctx):
    ttft = [r.token_times[0] - r.arrival_time for r in ctx.requests
            if ctx.t0 <= r.arrival_time < ctx.t1 and r.token_times]
    return float(np.median(ttft) * 1e3) if ttft else None
