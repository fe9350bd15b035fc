#!/usr/bin/env python3
"""Readings that the correctness limits are set from, at a cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --seconds <s>

One process (set-up is long, and the chip belongs to one process): the
cell's problem is built once; for each seed the inputs are made anew
(``reseed``), a short window runs at the cell's own load, and the numbers
the run would compare are read.  On the control seeds the control (the
plain reference in the next lower precision, put in the program's place on
the same inputs) is read as well.  One JSON line per seed, then a summary:
the largest program reading and the smallest control reading of each
number.  Not part of a benchmark run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import importlib
    from bench import harness
    from repro.compile_cache import enable_compile_cache

    cell, config, traffic = harness.resolve(harness.load_spec(), args.workload)
    device = harness.check_device(cell["chips"], harness.load_peaks())
    print(f"device {device}; compile cache {enable_compile_cache()}",
          flush=True)
    system = importlib.import_module(f"bench.systems.{config['system']}")
    log = lambda *a: print(*a, flush=True)
    problem = None
    worst, least = {}, {}
    for seed in args.seeds:
        t = time.perf_counter()
        if problem is None:
            problem = system.build(config, traffic, seed, log)
        else:
            problem.reseed(seed)
        problem.window(args.seconds)
        r = problem.readings(with_control=seed in args.control_seeds)
        for k, v in r["program"].items():
            worst[k] = max(worst.get(k, v), v)
        for k, v in (r["control"] or {}).items():
            least[k] = min(least.get(k, v), v)
        print(json.dumps({"seed": seed, **r,
                          "seconds": time.perf_counter() - t}), flush=True)
    print(json.dumps({"largest_program": worst, "smallest_control": least,
                      "limits": config["limits"],
                      "total_s": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
