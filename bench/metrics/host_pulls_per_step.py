"""host_pulls_per_step (engine): ``host_pull`` program spans (device to
host reads through ``repro.spans.pull``) that start in the window, over the
window's ``sched/step`` spans."""
from bench import program_spans


def read(ctx):
    sp = program_spans.window_spans(ctx)
    if sp is None:
        return None
    steps = sum(1 for s in sp if s.name == program_spans.STEP)
    pulls = sum(1 for s in sp if s.name == program_spans.PULL)
    return pulls / steps if steps else None
