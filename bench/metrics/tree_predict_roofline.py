"""The traversal kernel's share of its roofline: ``work.py``'s lower-bound
time of the window's calls (its mean over the checked calls, times the
calls) over the summed device time of the kernel's events in the trace.
Nothing to read without a trace, kernel events or a work count."""


def read(ctx):
    trace, per_call = ctx.trace, ctx.bound_s_per_call
    if trace is None or not trace.kernel_s or per_call is None:
        return None
    return 100.0 * per_call * len(ctx.window.calls) / trace.kernel_s
