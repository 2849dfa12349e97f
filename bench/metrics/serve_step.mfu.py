"""The whole served call's share of the chip's peak: ``work.py``'s
lower-bound time of the window's calls over their wall time on the host
clock (from the call into ``ForestServer.serve`` to its answer).  It bounds
every kernel roofline of the path: a kernel taken off the path leaves its
own share silent, but not this one."""


def read(ctx):
    per_call = ctx.bound_s_per_call
    calls = ctx.window.calls
    if ctx.trace is None or per_call is None or not calls:
        return None
    wall = sum(t1 - t0 for t0, t1, _ in calls)
    return 100.0 * per_call * len(calls) / wall
