"""tier_host_ms (tiers): median, over the window's ``sched/step`` program
spans, of the host time the ``tier/*`` spans under the step cover (their
union): observation feeds, KV flushes and daemon ticks."""
import numpy as np

from bench import program_spans


def read(ctx):
    sp = program_spans.window_spans(ctx)
    if sp is None:
        return None
    ms = program_spans.per_step_ms(sp, ("tier/",), self_time=False)
    return float(np.median(ms)) if ms else None
