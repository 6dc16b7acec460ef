"""sched_self_ms (scheduler): median, over the window's ``sched/step``
program spans, of the step's duration less the part of it that the
``engine/*`` and ``tier/*`` spans under it cover: the scheduler's own host
time per step (admission, metering, sampling, bookkeeping)."""
import numpy as np

from bench import program_spans


def read(ctx):
    sp = program_spans.window_spans(ctx)
    if sp is None:
        return None
    ms = program_spans.per_step_ms(sp, ("engine/", "tier/"), self_time=True)
    return float(np.median(ms)) if ms else None
