"""Benchmark runner: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig2,...]

Prints ``name,us_per_call,derived`` CSV (plus a header comment per suite).
``--obs-out PATH`` additionally dumps the repro.obs metrics snapshot
(shard balance, build counters, span timings the suites accumulated) as
JSON — the bench lane writes OBS_bench.json next to the BENCH_*.json
artifacts so every gated run ships its observability context.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = ("table1", "fig2", "index_build", "kernels", "snrm", "dist",
          "partitioned", "retrieval", "compressed", "frontend", "live")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--obs-out", default=None,
                    help="write the repro.obs metrics snapshot here after "
                         "all suites (.json -> JSON, else Prometheus text)")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else SUITES
    from repro import use_compile_cache
    use_compile_cache()

    failures = 0
    print("name,us_per_call,derived")
    for suite in SUITES:
        if suite not in only:
            continue
        t0 = time.time()
        print(f"# --- {suite} ---", flush=True)
        try:
            mod = __import__(f"benchmarks.bench_{suite}",
                             fromlist=["main"])
            mod.main()
        except Exception:
            failures += 1
            print(f"# {suite} FAILED:", file=sys.stderr)
            traceback.print_exc()
        print(f"# {suite} done in {time.time()-t0:.1f}s", flush=True)
    if args.obs_out:
        from repro import obs
        obs.write_metrics(args.obs_out)
        print(f"# obs snapshot -> {args.obs_out}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
