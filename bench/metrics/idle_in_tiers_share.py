"""idle_in_tiers_share (tiers): device idle time in the traced window
during which the innermost open program span is a ``tier/*`` span (or a
``host_pull`` under one), over the window, in percent.  Silent where the
trace holds no device op (CPU) or the program records no spans.

The whole table is printed on stderr: device idle seconds in the window by
the innermost open program span (``host_pull`` by site, with the span it
ran under), and ``none``; they add up to the window's idle time."""
import json
import sys

from bench import program_spans, trace


def read(ctx):
    if ctx.trace is None or not ctx.trace["ops"]:
        return None
    sp = program_spans.window_spans(ctx)
    if sp is None:
        return None
    got = program_spans.idle_by_span(ctx.trace, sp, ctx.t0, ctx.t1)
    if got is None:
        return None
    table, tier_s = got
    idle = trace.window_s(ctx.trace) - trace.busy_s(ctx.trace)
    print(f"idle by program span (sum {sum(v for _, v in table)} s of "
          f"{idle} s idle): {json.dumps(table)}", file=sys.stderr)
    return 100.0 * tier_s / trace.window_s(ctx.trace)
