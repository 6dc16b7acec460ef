"""itl_p95_ms: 95th percentile of the gaps between consecutive output
tokens of a request, over every gap whose two tokens both fall in the
window."""
import numpy as np


def read(ctx):
    gaps = []
    for r in ctx.requests:
        t = np.asarray([x for x in r.token_times if ctx.t0 <= x <= ctx.t1])
        gaps.extend(np.diff(t))
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None
