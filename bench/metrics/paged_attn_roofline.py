"""paged_attn_roofline (kernels): the least time the ``paged_attn`` kernel's
work needs at the device's peaks, over the kernel's device time in the
trace, in percent.  The work is counted from shapes (bench/work.py, with
the architecture module's ``paged_attn_call`` and ``attn_layers``): K and
V of the keys every active lane attends, plus the query and the output,
per layer and decode body -- the same whatever implements it.  Calls on
behalf of lanes that are not active (the other lanes of a chunk scan) are
not work.  The bound that sets the least time is printed on stderr."""
import sys

from bench import trace, work

KERNEL = "paged_attn"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = trace.op_seconds(ctx.trace, lambda n: KERNEL in n)
    if kernel_s <= 0:
        return None
    least, bound = work.paged_attn_least_s(
        ctx.arch, ctx.f, [ps for _, ps in ctx.bodies], ctx.peaks,
        ctx.geo["page_t"], ctx.geo["ring_pages"])
    print(f"paged_attn: least {least} s ({bound} bound), device {kernel_s} s",
          file=sys.stderr)
    return 100.0 * least / kernel_s
