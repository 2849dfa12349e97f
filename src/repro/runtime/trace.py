"""Named host spans of the served call, for the JAX profiler.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: with no
profiler running it costs about a microsecond to enter and leave (a
little more with keyword arguments), and about 2 us when the profiler
records it.  There is no recorder here: the profiler keeps the spans with
the device's operations, on the same clock, and writes them out when the
trace stops.  Keyword arguments become the event's stats; the event keeps
its plain name.

Every engine (``pipelined``, ``sharded``, ``simple``) uses the same names,
so a breakdown by stage does not depend on which engine ran:

===================== ==================================================
Span                  Covers
===================== ==================================================
``serve.call``        ``ForestServer.serve`` / ``serve_safe``: the whole
                      call.  Args: ``seq`` (the server's call counter),
                      ``rows``.
``serve.plan``        ``ForestServer.plan``: key and token, plan-cache
                      lookup, ``build_plan`` on a miss.
``serve.prep``        host work on the rows: ``execute``'s checks and
                      ``concat_rows``, each engine's row permutation, and
                      the kernel wrappers' host float32-range check.
``serve.pack``        ``_gathered_pack`` (sweep and lookup; on a miss the
                      gather, arena admission and decode included), and
                      ``pack_host_tiles`` on the ``simple`` engine.
``tree_predict.upload`` the kernel wrappers' host-to-device transfers of
                      the rows, segment ids and chunk ranges (and tree
                      tiles on the ``simple`` engine).
``tree_predict.launch`` the jitted kernel call until it returns: dispatch
                      only, the kernel runs on after it.  Arg:
                      ``path`` (the traversal body, ``gemm`` or
                      ``walk``).
``serve.wait``        each engine's copy of the answer back to the host,
                      which first waits for the device.
``serve.finalize``    each engine's un-permute, and
                      ``ForestServer._finalize``.
===================== ==================================================

``serve.prep`` and ``serve.finalize`` open more than once in a call; each
interval counts.  Under the ``Scheduler`` a batch is planned on the
submitting thread and executed on the worker, so its stage spans have no
``serve.call`` around them.

Capture them with the profiler around the calls to look at::

    jax.profiler.start_trace("/path/to/logdir")
    server.serve(requests)
    jax.profiler.stop_trace()

and open the ``.xplane.pb`` (TensorBoard, Perfetto, or
``jax.profiler.ProfileData.from_file``).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A context manager that marks ``name`` on the host's trace line for
    as long as it is open; ``args`` go into the event's stats."""
    return TraceAnnotation(name, **args)
