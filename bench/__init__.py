"""The chip benchmark of the compressed-forest server.

One command runs one cell of ``BENCHMARK.json`` once:

    python bench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by its name:

* ``bench/configs/<config>.json`` — the deployment and its data seed;
* ``bench/traffic/<traffic>.json`` — the loop kind and its parameters,
  read by the one generator in ``traffic.py``;
* ``bench/metrics/<metric>.py`` — a reader with ``read(ctx)`` that
  returns the metric's value, or ``None`` where it finds nothing to read.

The yardstick lives here too: the traffic generator, the numpy reference
(``reference.py``), the work count behind every roofline share
(``work.py``), the table of peaks (``peaks.json``) and the reduction of
profiler traces (``devtrace.py``).  From the program the benchmark takes
only the system under test, its counters and its kernel names.
"""
