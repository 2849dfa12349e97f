"""The served call's stages, from the program's own spans in the profiler
trace.

The program marks each stage of ``ForestServer.serve`` with a span
(``repro.runtime.trace``: ``serve.*`` and ``tree_predict.*``).  The
profiler writes them into the same ``.xplane.pb`` as the device's
operations and the benchmark's ``bench.*`` spans, on the same clock.  This
module reads them beside ``devtrace``, and leaves ``devtrace`` and every
number it gives as they are:

* ``read_spans`` keeps every host span whose name starts with one of
  ``PREFIXES``, with its host line (thread);
* ``span_table`` gives, per name, the count, the summed duration and the
  summed self time of the spans inside the window.  A span's self time is
  its duration less the union of the spans nested in it on its line;
* ``idle_by_span`` puts each idle gap of the device down to the innermost
  span that covers its middle, ``devtrace.summarize``'s rule, by a sweep
  that stays fast with hundreds of thousands of spans;
* ``idle_in_spans`` splits the idle time across the stages instead: each
  name gets the idle time inside its self intervals.

Run as a script it serves one cell of ``BENCHMARK.json`` as ``bench/run.py``
does (same set-up and warm-up, same closed loop), then measures windows
with the profiler off and on in turn, and prints one JSON line per window:
``rows_per_s``, and for a traced window the stages per call, the idle gaps
by stage and the checks that the spans cover the call::

    python bench/stages.py --workload forests_rf500.rows1 --seed 7 \\
        --seconds 20 --windows 0,1,0,1,0,1

It checks no answer against the reference; ``bench/run.py`` does that.
"""
from __future__ import annotations

import bisect
import heapq
import sys
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":  # run as a script: the checkout's packages
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import devtrace  # noqa: E402

#: host spans read: the benchmark's own and the program's stages
PREFIXES = ("bench.", "serve.", "tree_predict.")
#: the program's stages of one served call, in the order they run
STAGES = ("serve.plan", "serve.prep", "serve.pack", "tree_predict.upload",
          "tree_predict.launch", "serve.wait", "serve.finalize")


@dataclass
class Span(devtrace.Event):
    line: str = ""  # host plane and line: spans nest only on one line


def read_spans(path: Path) -> list[Span]:
    """Host spans of ``PREFIXES`` in one ``.xplane.pb``, with their line."""
    from jax.profiler import ProfileData

    out: list[Span] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            key = f"{plane.name}#{i}"
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns, "", key))
    return out


def window(spans: list[Span]) -> tuple[float, float] | None:
    """The ``bench.window`` bounds, as ``devtrace.summarize`` takes them."""
    ws = [s for s in spans if s.name == "bench.window"]
    if not ws:
        return None
    return min(s.start_ns for s in ws), max(s.end_ns for s in ws)


def self_intervals(spans: list[Span]) -> list[list[tuple[float, float]]]:
    """Each span's interval less the union of the spans nested in it on
    its line: the stretches in which it is the innermost span of its
    line, in the order given."""
    out: list[list[tuple[float, float]]] = [[] for _ in spans]
    by_line: dict[str, list[int]] = defaultdict(list)
    for i, s in enumerate(spans):
        by_line[s.line].append(i)
    for idx in by_line.values():
        idx.sort(key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
        reach = {i: spans[i].start_ns for i in idx}  # covered up to here
        stack: list[int] = []
        for i in idx:
            s = spans[i]
            while stack and not (spans[stack[-1]].start_ns <= s.start_ns
                                 and s.end_ns <= spans[stack[-1]].end_ns):
                stack.pop()
            if stack:
                p = stack[-1]
                if s.start_ns > reach[p]:
                    out[p].append((reach[p], s.start_ns))
                reach[p] = max(reach[p], s.end_ns)
            stack.append(i)
        for i in idx:
            if spans[i].end_ns > reach[i]:
                out[i].append((reach[i], spans[i].end_ns))
    return out


def self_times(spans: list[Span]) -> list[float]:
    """Each span's duration less the union of the spans nested in it on
    its line, in ns, in the order given."""
    return [sum(e - s for s, e in iv) for iv in self_intervals(spans)]


def span_table(spans: list[Span], lo: float, hi: float) -> dict:
    """``{name: (count, total_s, self_s)}`` of the spans inside
    ``[lo, hi]``."""
    inside = [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]
    table: dict[str, list[float]] = defaultdict(lambda: [0, 0.0, 0.0])
    for s, own in zip(inside, self_times(inside)):
        row = table[s.name]
        row[0] += 1
        row[1] += s.dur_ns / 1e9
        row[2] += own / 1e9
    return {k: (int(n), tot, own) for k, (n, tot, own) in table.items()}


def per_call_ms(table: dict, name: str) -> float | None:
    """``name``'s summed self time over the number of ``bench.call``
    spans, in ms; ``None`` without either."""
    calls = table.get("bench.call", (0, 0.0, 0.0))[0]
    if name not in table or not calls:
        return None
    return 1e3 * table[name][2] / calls


def device_gaps(devices: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which no chip ran an operation."""
    merged = []
    for events in devices.values():
        inside = [(e.start_ns, e.end_ns) for e in events
                  if e.end_ns > lo and e.start_ns < hi]
        merged.extend(devtrace._union(devtrace._clip(inside, lo, hi)))
    gaps, t = [], lo
    for s, e in devtrace._union(merged) + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    return gaps


def idle_by_span(gaps, spans: list[devtrace.Event]) -> dict[str, float]:
    """Seconds of the gaps put down to the innermost (shortest) span that
    covers each gap's middle, ``bench.window`` where none does; ties go to
    the span listed first, as in ``devtrace.summarize``."""
    order = sorted((s for s in enumerate(spans)
                    if s[1].name != "bench.window"),
                   key=lambda s: s[1].start_ns)
    heap: list[tuple[float, int, float, str]] = []
    out: dict[str, float] = defaultdict(float)
    k = 0
    for s, e in sorted(gaps):
        mid = (s + e) / 2
        while k < len(order) and order[k][1].start_ns <= mid:
            i, h = order[k]
            heapq.heappush(heap, (h.dur_ns, i, h.end_ns, h.name))
            k += 1
        while heap and heap[0][2] <= mid:
            heapq.heappop(heap)
        out[heap[0][3] if heap else "bench.window"] += e - s
    return {name: v / 1e9 for name, v in out.items()}


def idle_in_spans(gaps, spans: list[Span]) -> dict[str, float]:
    """Seconds of the gaps that fall in each name's self intervals: the
    device's idle time split across the stages it overlaps.  On one line
    the parts add up to the gaps; a gap counts once on each line."""
    gaps = sorted(gaps)
    starts = [s for s, _ in gaps]
    ends = [e for _, e in gaps]
    cum = [0.0]
    for s, e in gaps:
        cum.append(cum[-1] + e - s)

    def idle(lo, hi):
        j0 = bisect.bisect_right(ends, lo)
        j1 = bisect.bisect_left(starts, hi)
        if j1 <= j0:
            return 0.0
        return (cum[j1] - cum[j0] - max(0.0, lo - starts[j0])
                - max(0.0, ends[j1 - 1] - hi))

    out: dict[str, float] = defaultdict(float)
    for s, iv in zip(spans, self_intervals(spans)):
        for lo, hi in iv:
            out[s.name] += idle(lo, hi)
    return {name: v / 1e9 for name, v in out.items()}


def kernel_in_call(devices: dict, spans: list[Span]) -> float | None:
    """Share of the kernel's device events that start after a
    ``tree_predict.launch`` begins and end before the first ``serve.wait``
    that follows it on that line ends."""
    launches = sorted((s.start_ns, s.line) for s in spans
                      if s.name == "tree_predict.launch")
    waits: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for s in spans:
        if s.name == "serve.wait":
            waits[s.line].append((s.start_ns, s.end_ns))
    for w in waits.values():
        w.sort()
    starts = [t for t, _ in launches]
    n = ok = 0
    for events in devices.values():
        for e in events:
            if not devtrace.is_kernel(e):
                continue
            n += 1
            j = bisect.bisect_right(starts, e.start_ns) - 1
            if j < 0:
                continue
            t, line = launches[j]
            w = waits[line]
            k = bisect.bisect_left(w, (t, float("-inf")))
            ok += k < len(w) and e.end_ns <= w[k][1]
    return ok / n if n else None


def breakdown(devices: dict, spans: list[Span]) -> dict | None:
    """What one traced window says of the served call."""
    bounds = window(spans)
    if bounds is None:
        return None
    lo, hi = bounds
    bench = [s for s in spans if s.name.startswith("bench.")]
    summary = devtrace.summarize(devices, bench)
    table = span_table(spans, lo, hi)
    calls = table.get("bench.call", (0, 0.0, 0.0))
    served = table.get("serve.call", (0, 0.0, 0.0))
    out = {
        "calls": calls[0],
        "stages_ms_per_call": {n: per_call_ms(table, n)
                               for n in STAGES + ("serve.call", "bench.call",
                                                  "bench.window")},
        "span_table": table,
        "serve_call_self_share": served[2] / served[1] if served[1] else None,
        "serve_call_over_bench_call": (served[1] / calls[1]
                                       if calls[1] else None),
    }
    if summary is not None:
        in_call = [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]
        gaps = device_gaps(devices, lo, hi)
        host = [d - b for d, b in summary.calls]
        out.update({
            "idle_share": summary.idle_share,
            "kernel_s": summary.kernel_s,
            "kernel_events": summary.kernel_events,
            "host_ms_per_batch": (1e3 * sum(host) / len(host)
                                  if host else None),
            "device_ops": summary.device_ops,
            "idle_gaps_bench": summary.idle_gaps,
            "idle_gaps": sorted(idle_by_span(gaps, in_call).items(),
                                key=lambda kv: -kv[1]),
            "idle_in_stages": sorted(idle_in_spans(gaps, in_call).items(),
                                     key=lambda kv: -kv[1]),
            "kernel_in_launch_to_wait": kernel_in_call(devices, spans),
        })
    return out


def main(argv=None, root: Path = ROOT) -> int:
    import argparse
    import gc
    import json
    import shutil
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", default="0,1",
                    help="1 traces a window, 0 does not, in this order")
    ap.add_argument("--out", help="also write the lines to this file")
    args = ap.parse_args(argv)

    import jax

    from bench import forests, harness
    from bench import traffic as tr

    cell = harness.load_cell(root, args.workload)
    harness.use_compile_cache(root / "bench" / ".cache" / "jax")
    path, _ = forests.ensure_built(root, cell.config)
    server, users = forests.load_server(cell.config, path)
    forests.upload(server, users)
    rows = tr.RowSource(args.seed, int(cell.config["n_features"]),
                        int(cell.config["n_bins"]))
    loop = harness.make_loop(cell.traffic, server, users, rows)
    loop.warm()
    gc.collect()
    gc.freeze()
    harness.stop_compile_cache()
    trace_dir = root / "bench" / ".cache" / "stages"
    lines = []
    for traced in (w == "1" for w in args.windows.split(",")):
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
        try:
            win = loop.run(args.seconds)
        finally:
            if traced:
                jax.profiler.stop_trace()
        line = {"workload": args.workload, "seed": args.seed,
                "traced": traced, "rows_per_s": win.rows / win.seconds,
                "calls": len(win.calls), "device": jax.devices()[0].device_kind}
        if traced:
            t0 = time.perf_counter()
            xplane = devtrace.find_xplane(trace_dir)
            devices, _ = devtrace.read_xplane(xplane)
            line["breakdown"] = breakdown(devices, read_spans(xplane))
            line["reduce_s"] = time.perf_counter() - t0
            shutil.rmtree(trace_dir, ignore_errors=True)
        lines.append(json.dumps(line))
        print(lines[-1], flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
