"""Share of the traced window in which no operation ran on the chip, in a
cell that reports ``rows_per_s``: 1 - (union of device op intervals) /
(window), from the profiler trace (``devtrace``)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
