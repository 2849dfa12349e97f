"""Readings for the limits, many seeds in one process.

    python bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--control-seeds 7 8 9]

Builds the cell's server once, then for each seed runs the cell's own
window and the check, and prints one JSON line of the numbers compared.
``--control-seeds`` does the same with the control (``control.py``: the
kernel's one-hot gather in bfloat16) put in the program's place; its
numbers are the upper readings of the limits.

The benchmark's own runs never run this.  It needs a TPU unless ``--cpu``
is given (the tests run it on the CPU at a small size).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import forests, harness, reference
    from bench import traffic as tr
    from bench.control import bf16_gathers

    root = Path(args.root)
    cell = harness.load_cell(root, args.workload)
    dev = jax.devices()[0]
    if not args.cpu:
        if dev.platform != "tpu":
            print(f"calibrate: needs a TPU, found {dev.platform!r}",
                  file=sys.stderr)
            return 3
        harness.use_compile_cache(root / "bench" / ".cache" / "jax")
    config, traffic = cell.config, cell.traffic
    path, _ = forests.ensure_built(root, config)
    server, users = forests.load_server(config, path)
    forests.upload(server, users)
    ref = reference.Forests.load(path / "forests.npz")

    def one(seed: int, label: str) -> dict:
        rows = tr.RowSource(seed, int(config["n_features"]),
                            int(config["n_bins"]))
        loop = harness.make_loop(traffic, server, users, rows)
        t0 = time.perf_counter()
        loop.warm()
        warm_s = time.perf_counter() - t0
        win = loop.run(args.seconds)
        t0 = time.perf_counter()
        numbers, compared, _ = harness.check(ref, win, rows, traffic, seed,
                                             config, None)
        line = {"label": label, "seed": seed, "numbers": numbers,
                "rows_compared": compared, "warm_s": warm_s,
                "check_s": time.perf_counter() - t0,
                "requests": len(win.requests), "window_s": win.seconds,
                "rows_per_s": win.rows / win.seconds}
        print(json.dumps(line), flush=True)
        return line

    for seed in args.seeds:
        one(seed, "program")
    if args.control_seeds:
        with bf16_gathers():
            for seed in args.control_seeds:
                one(seed, "control")
    return 0


if __name__ == "__main__":
    sys.exit(main())
