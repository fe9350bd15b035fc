"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Table/figure map:
  Table 1  -> bench_pingpong      Fig 5/9 -> bench_async
  Fig 10   -> bench_cg            Fig 11  -> bench_meshdist
  Fig 12   -> bench_spmm          (extra) -> bench_kernels
  §2 DMDA halo / unit sweep -> bench_halo
Roofline tables are produced by ``python -m repro.launch.roofline`` from the
dry-run reports.

Every suite that writes a ``BENCH_*.json`` artifact gets it stamped with
the run's :func:`repro.core.sflog.dump_json` summary (the events/counters
the suite generated in this process), so artifacts carry exchange/byte
provenance, not just timings.
"""

import argparse
import sys

# suite -> the artifact its run() writes (stamped with sflog provenance)
ARTIFACTS = {
    "pingpong": "BENCH_pingpong.json",
    "async": "BENCH_async.json",
    "kernels": "BENCH_kernels.json",
    "halo": "BENCH_halo.json",
    "serving": "BENCH_serving.json",
    "ddp": "BENCH_ddp.json",
    "assembly": "BENCH_assembly.json",
}


def _sflog_summary(before):
    """The suite-window slice of the registry: per-event count/bytes growth,
    exchange totals, and the full counter table."""
    from repro.core import sflog
    delta = sflog.events_delta(before)
    return {"mode": sflog.mode(),
            "events_delta": delta,
            "exchange_totals": sflog.exchange_totals(delta),
            "counters": sflog.counters()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma list: "
                         "pingpong,async,cg,meshdist,spmm,kernels,halo,"
                         "serving,ddp,assembly")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_assembly, bench_async, bench_cg, bench_ddp,
                            bench_halo, bench_kernels, bench_meshdist,
                            bench_pingpong, bench_serving, bench_spmm)
    suites = {
        "pingpong": bench_pingpong.run,
        "async": bench_async.run,
        "cg": bench_cg.run,
        "meshdist": bench_meshdist.run,
        "spmm": bench_spmm.run,
        "kernels": bench_kernels.run,
        "halo": bench_halo.run,
        "serving": bench_serving.run,
        "ddp": bench_ddp.run,
        "assembly": bench_assembly.run,
    }
    from benchmarks.artifacts import artifact_path, stamp_sflog
    from repro.core import sflog

    wanted = list(suites) if args.only == "all" else args.only.split(",")
    print("name,us_per_call,derived")
    ok = True
    for name in wanted:
        try:
            before = sflog.events_snapshot()
            for row in suites[name]():
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
            if name in ARTIFACTS:
                stamp_sflog(artifact_path(ARTIFACTS[name]),
                            _sflog_summary(before))
        except Exception as e:  # noqa: BLE001
            ok = False
            print(f"{name}_FAILED,0,{type(e).__name__}: {e}",
                  file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
