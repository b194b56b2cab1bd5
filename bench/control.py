#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip at its own size.

    python3 bench/control.py --workload <name> --seeds 11,12,13 \\
        --seconds 5 [--out FILE]

For each seed, in one process: build the cell's deployment, serve a short
window of its mix at its own rate and load, probe the checked requests and
free the index, exactly as a run does.  Then compare with the reference
twice:

* the program: the served scores and the probe's M against the reference
  at ``highest`` (what a run compares: the lower reading);
* the control: the same reference computed at ``high``, three bfloat16
  passes, the precision below the configuration's float32 at ``highest``,
  with the provider's part at its file's ``CONTROL_PRECISION`` where it
  names one (``harness.control``), put in the program's place (the upper
  reading; it has to fail a limit).

One JSON line per seed on standard output (and in ``--out``).  The
benchmark's own runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def readings(H, traffic, cell, seed: int, seconds: float) -> dict:
    """Program and control gaps of one seed, as a run would take them."""
    mix = cell.mix
    t = time.perf_counter()
    dep = H.Deployment(cell.config, seed)
    H.warm_up(dep, mix, seed)
    reqs = traffic.schedule(mix, dep.corpus, seed, seconds)
    setup = time.perf_counter() - t
    win = H.open_loop(dep, mix, reqs, seconds)
    served = [i for i, s in enumerate(win.status) if s == "ok"]
    idx = [served[i] for i in traffic.check_sample(
        [reqs[i] for i in served], mix, seed)]
    got_m = H.probe(dep, mix, reqs, idx)
    dep.release()
    want = H.reference(dep, reqs, idx, "highest")
    ctrl = H.control(dep, reqs, idx)
    checks = H.compare(win, reqs, idx, got_m, want, mix["checks"])
    program = H.gaps([win.scores[i] for i in idx], got_m, want)
    control = H.gaps([c[1] for c in ctrl], [c[0] for c in ctrl], want)
    fns = cell.config["functions"]
    per_f = dict(zip(fns, H.m_by_function(got_m, want)))
    ctrl_f = dict(zip(fns, H.m_by_function([c[0] for c in ctrl], want)))
    worst = max(per_f, key=lambda f: per_f[f]["gap"])
    q_len, n_b = got_m[0].shape[1], got_m[0].shape[2]
    doc, rest = divmod(per_f[worst]["at"], q_len * n_b)
    starts = np.cumsum([0] + [len(reqs[i].docs) for i in idx])
    k = int(np.searchsorted(starts, doc, side="right") - 1)
    r = reqs[idx[k]]
    slot, seg = divmod(rest, n_b)
    return {"seed": seed, "setup_s": setup, "requests": len(reqs),
            "checked": len(idx),
            "terms_mean": float(np.mean([(reqs[i].terms >= 0).sum()
                                         for i in idx])),
            "program": dict(program, lost=checks["lost"]["value"]),
            "control": control,
            "program_by_function": per_f,
            "control_by_function": {f: {"gap": v["gap"], "rms": v["rms"]}
                                    for f, v in ctrl_f.items()},
            "widest": {"function": worst, "request": idx[k],
                       "doc": int(r.docs[doc - starts[k]]),
                       "slot": int(slot), "term": int(r.terms[slot]),
                       "terms": int((r.terms >= 0).sum()),
                       "segment": int(seg)},
            "program_correct": H.is_correct(checks),
            "control_correct": H.is_correct(
                {k: {"value": control[k], "limit": v}
                 for k, v in mix["checks"].items()})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import harness as H
    from bench import traffic

    cell = H.load_cell(args.workload)
    H.setup_jax(cell.config)
    try:
        H.device_info(cell.chips)
    except H.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    out = open(args.out, "w") if args.out else None
    for s in args.seeds.split(","):
        line = json.dumps(readings(H, traffic, cell, int(s), args.seconds))
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
