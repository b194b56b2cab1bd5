#!/usr/bin/env python3
"""Find a cell's knee: its mix at a list of fixed rates, in one process.

    python3 bench/sweep.py --workload <name> --seed <n> --rates 5,20,40 \\
        --seconds 10 [--retrieve 20] [--out FILE]

Builds the cell's deployment once, warms it, then offers each rate for
``--seconds`` through a fresh frontend and reports per rate: p50 and p95 latency from the due time, how
late the generator ran, and the backlog, i.e. requests due in the window
and not yet answered when it closed (a backlog that grows with the window
is a rate past the knee).  The knee is the highest rate whose p95 meets
the cell's limit with no growing backlog; the lowest rate gives the
unloaded p50 that the limit is a multiple of.

``--retrieve N`` also times ``SeineEngine.retrieve`` (k = 10) on N of the
corpus's queries, one at a time, after one compiling call.

Each line of standard output is one JSON object; ``--out`` also writes
them to a file.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--retrieve", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness as H
    from bench import traffic

    cell = H.load_cell(args.workload)
    H.setup_jax(cell.config)
    import jax

    try:
        device = H.device_info(cell.chips)
    except H.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    counter = H.CompileCounter()
    out = open(args.out, "w") if args.out else None

    def emit(**kv):
        line = json.dumps(kv)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    mix = cell.mix
    dep = H.Deployment(cell.config, args.seed,
                       lambda phase, **kv: emit(phase=phase, **kv))
    H.warm_up(dep, mix, args.seed)
    emit(phase="setup", s=time.perf_counter() - T0, device=device,
         peak_bytes=(jax.devices()[0].memory_stats() or {}).get(
             "peak_bytes_in_use"))
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = traffic.schedule(mix, dep.corpus, args.seed + i,
                                args.seconds, rate=rate)
        win = H.open_loop(dep, mix, reqs, args.seconds,
                          counter=counter)
        lat = win.latency_ms
        close = win.start + win.seconds
        backlog = int(np.sum(~(win.done <= close)))
        half = win.start + win.seconds / 2
        due_first = win.due < half
        backlog_half = int(np.sum(due_first & ~(win.done <= half)))
        emit(phase="rate", rate=rate, requests=len(reqs),
             p50_ms=float(np.percentile(lat, 50)),
             p95_ms=float(np.percentile(lat, 95)),
             max_ms=float(lat.max()),
             late_p95_ms=float(np.percentile(win.late_ms, 95)),
             queue_ms=win.queue_ms, backlog_at_close=backlog,
             backlog_at_half=backlog_half, compiles=win.compiles,
             errors=len(win.errors))
    if args.retrieve:
        q = dep.corpus.queries
        t = time.perf_counter()
        jax.block_until_ready(dep.engine.retrieve(q[0], 10))
        first = time.perf_counter() - t
        ms = []
        for j in range(1, args.retrieve + 1):
            t = time.perf_counter()
            jax.block_until_ready(dep.engine.retrieve(q[j % len(q)], 10))
            ms.append((time.perf_counter() - t) * 1e3)
        emit(phase="retrieve", k=10, first_call_s=first, queries=len(ms),
             p50_ms=float(np.median(ms)), max_ms=float(max(ms)))
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
