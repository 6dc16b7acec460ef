"""hbm_peak_gb (device): the device allocator's ``peak_bytes_in_use``
after the window, in GB (1e9 bytes)."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
