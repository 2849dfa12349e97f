"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python bench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  Without a TPU, or with fewer chips, it exits nonzero and
prints no result; it never falls back to the CPU.  The last line of
standard output is the result (JSON); the last lines of standard error
are the numbers compared with the reference, each beside its limit.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime's logs stay inside the checkout, not under /tmp
    if "TPU_LOG_DIR" not in os.environ:
        logs = ROOT / "bench" / ".cache" / "tpu_logs"
        logs.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(logs)
    from bench.harness import NoChip, run_cell

    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result, allow_nan=False))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # leave at once: nothing may print after the checks, and every thread
    # the run started has been joined
    os._exit(code)
