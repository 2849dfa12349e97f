"""Host time per served call, in milliseconds: each ``bench.call`` span
of the trace minus the device busy time inside it, averaged over the
traced calls."""


def read(ctx):
    trace = ctx.trace
    if trace is None or not trace.calls:
        return None
    host = [dur - busy for dur, busy in trace.calls]
    return 1e3 * sum(host) / len(host)
