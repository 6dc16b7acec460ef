"""tier_device_share (tiers): device time in the tiers' own programs over
the traced window, in percent.  The programs, by the name the trace gives
them (``jit_<function>``): the async epoch copy and its fast-buffer
refreshes, row copies and gathers (``tiering/migrate.py``), the NeoProf
observe, sketch histogram and counter drain (``tiering/memory.py``,
``core``), and ``jit__unknown``: the KV page flush, row writes and sync
epoch copy, which ``migrate.py`` jits as ``functools.partial`` objects,
whose name the trace does not know."""
from bench import trace

PROGRAMS = ("_issue_migrate_jit", "_refresh_pages_impl", "_refresh_rows_impl",
            "_refresh_copy_impl", "_copy_rows_impl", "_gather_jit", "observe",
            "sketch_histogram", "drain_period_stats", "_unknown")
PREFIXES = tuple(f"jit_{p}(" for p in PROGRAMS)


def read(ctx):
    if ctx.trace is None:
        return None
    spent = trace.op_seconds(ctx.trace, lambda n: n.startswith(PREFIXES),
                             source="modules")
    return 100.0 * spent / trace.window_s(ctx.trace)
