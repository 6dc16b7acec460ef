"""decode_call_ms (engine): median wall time of an ``advance_lanes`` call,
which ends on the host sync of the step's logits."""
import numpy as np


def read(ctx):
    d = [b - a for name, a, b in ctx.spans if name == "advance_lanes"]
    return float(np.median(d) * 1e3) if d else None
