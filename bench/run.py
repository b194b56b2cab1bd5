#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's deployment from ``--seed`` (corpus, weights, the
configuration's provider, and its engine: the index through
``IndexBuilder.build_partitioned`` and ``SeineEngine``, or ``NoIndexEngine``
over the documents' tokens) and warms every shape the cell's traffic uses.
The window then offers the mix's open-loop load to
``ServingFrontend.submit`` for ``--seconds``; every answer is waited for
until a minute past its close.  Afterwards the probe computes M of a
sample of the window's requests as the engine does, the engine is freed,
and the plain reference checks the sample: the scores the client received
and the probe's M.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` (a
window under the profiler's device tracer) its per-layer metrics with the
device's busy time and a breakdown.  The last line of standard output is
one JSON object; the numbers compared, each beside its limit, are the last
lines of standard error and the result's last key.  Without a TPU of a
kind ``bench/peaks.json`` knows, or with fewer chips than the cell needs,
it prints no result and exits 2.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import numpy as np

    from bench import harness as H
    from bench import trace as TR
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
    mix = cell.mix

    dep = H.Deployment(cell.config, args.seed, log)
    t = time.perf_counter()
    H.warm_up(dep, mix, args.seed)
    reqs = traffic.schedule(mix, dep.corpus, args.seed, args.seconds)
    log("warm", s=time.perf_counter() - t, requests=len(reqs),
        compiles=counter.n)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    mark = None
    if tdir:
        mark = H.ClockMark()
        # device ops only: nothing reads host events, and the host and
        # Python tracers load the host the window measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - T0

    win = H.open_loop(dep, mix, reqs, args.seconds, mark=mark,
                      counter=counter)
    n_ok = int(win.served.sum())
    terms = np.array([int((r.terms >= 0).sum()) for r in reqs])
    lat = win.latency_ms
    worst = np.argsort(-lat)[:3]
    log("window", due=len(reqs), served=n_ok,
        errors=len(win.errors),
        missing=win.status.count("missing"),
        compiles_in_window=win.compiles,
        terms_mean=float(terms.mean()) if terms.size else 0.0,
        terms_hist=np.bincount(terms).tolist(),
        gc_pauses=len(win.gc_pauses),
        gc_max_ms=max((p[1] * 1e3 for p in win.gc_pauses), default=0.0),
        worst_ms=[round(float(lat[i]), 1) for i in worst],
        worst_due_s=[round(float(reqs[i].due_s), 2) for i in worst],
        worst_late_ms=[round(float(win.late_ms[i]), 1) for i in worst],
        late_max_ms=round(float(np.nanmax(win.late_ms)), 1) if reqs else 0,
        host_stalls=len(win.host_stalls),
        host_stall_max_ms=round(max((x[1] * 1e3 for x in win.host_stalls),
                                    default=0.0), 1),
        host_stalls_at=[(round(t, 2), round(x * 1e3)) for t, x in
                        win.host_stalls if x > 0.2])
    for e in win.errors[:3]:
        log("error", what=e)
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    served = [i for i, s in enumerate(win.status) if s == "ok"]
    idx = traffic.check_sample([reqs[i] for i in served], mix, args.seed)
    idx = [served[i] for i in idx]
    t = time.perf_counter()
    got_m = H.probe(dep, mix, reqs, idx) if idx else []
    if tdir:
        jax.profiler.stop_trace()
    dep.release()
    want = H.reference(dep, reqs, idx) if idx else []
    log("check", requests=len(idx), s=time.perf_counter() - t)
    doc_tokens = H.doc_tokens(dep.corpus, reqs, idx)
    if idx:
        # what an encoder would encode of the checked requests, against
        # the candidates and positions the frontend and the corpus pad to
        real = sum(int(n.sum()) for n in doc_tokens)
        slots = sum(len(H.padded(reqs[i].docs, mix["batch_pad"]))
                    for i in idx) * dep.corpus.tokens.shape[1]
        log("work", doc_tokens_per_request=real / len(idx),
            padded_slots_per_request=slots / len(idx),
            real_share=real / slots)
    checks = H.compare(win, reqs, idx, got_m, want, mix["checks"]) \
        if idx else {"lost": {"value": len(reqs), "limit": 0}}
    if idx:
        # per atomic function the probe's widest and root-mean-square gap
        log("m", **{f: f"{v['gap']:.4g}/{v['rms']:.4g}" for f, v in zip(
            cell.config["functions"], H.m_by_function(got_m, want))})
    correct = H.is_correct(checks)

    view = H.RunView(window=win, mix=mix, config=cell.config,
                     setup_s=setup_s, memory_peak_bytes=peak,
                     probe_requests=[(reqs[i].terms, len(reqs[i].docs))
                                     for i in idx],
                     probe_doc_tokens=doc_tokens,
                     peaks=H.peaks(device["kind"]))
    breakdown = None
    if tdir:
        tr = TR.load(TR.find_xplane(tdir))
        off = TR.clock_offset(tr, H.MARK_KEY, mark.host_ns)
        lo, hi = TR.window(win.start, win.seconds, off)
        flight = [(a * 1e9 + off, b * 1e9 + off)
                  for a, b in zip(win.submitted, win.done)
                  if np.isfinite(a) and np.isfinite(b)]
        view.trace, view.trace_window = tr, (lo, hi)
        device["busy_s"] = TR.busy_s(tr, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": TR.top_ops(tr, lo, hi),
                     "idle_gaps": TR.idle_gaps(tr, lo, hi, flight)}
        shutil.rmtree(tdir, ignore_errors=True)
    device["memory_peak_bytes"] = peak
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = H.reader(m["name"])(view)
        if v is not None:
            metrics[m["name"]] = (v, m["unit"])

    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(H.result_line(correct, len(reqs),
                        len(win.errors) + win.status.count("missing"),
                        metrics, device, checks, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
