"""The served program's device time, on a small recorded trace of its own,
and a run without tracing, which records no span events.

One TPU plane; the window is host 0 .. 10 ms, which the clock mark puts
at trace 1 .. 11 ms:

    modules  [0.9, 1.0) jit_bench_clock_mark (host 0 once it finished)
             [0.5, 0.9) jit_seine_score, before the window
             [2.0, 3.5), [6.0, 6.5) jit_seine_score, in the window
             [10.5, 12.0) jit_seine_score, started in the window
             [11.5, 12.5) jit_seine_score, after the window
             [4.0, 5.0) jit__score_impl (the served program's old name)
             [12.0, 12.6) jit_bench_lookup_probe
    ops      [2.0, 3.5), [6.0, 6.5), [10.5, 12.0) fusion.3
"""
import numpy as np
import pytest

from bench import harness as H
from bench import trace as TR

MS = 1_000_000   # ns


def _event(meta, start_ms, end_ms):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * 1e9)}"
            f" duration_ps: {int((end_ms - start_ms) * 1e9)} }}")


MODULES = {1: "jit_bench_clock_mark(2)", 2: "jit_seine_score(51)",
           3: "jit__score_impl(4)", 4: "jit_bench_lookup_probe(9)"}
RUNS = [(1, 0.9, 1.0), (2, 0.5, 0.9), (2, 2.0, 3.5), (2, 6.0, 6.5),
        (2, 10.5, 12.0), (2, 11.5, 12.5), (3, 4.0, 5.0), (4, 12.0, 12.6)]


def _xspace(runs):
    meta = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: '
                    f'"{n}" }} }}' for k, n in MODULES.items())
    ops = " ".join(_event(9, s, e) for _, s, e in RUNS[2:5])
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {" ".join(_event(m, s, e) for m, s, e in runs)} }}
  {meta}
  event_metadata {{ key: 9 value {{ id: 9 name: "fusion.3" }} }}
}}
"""


def _load(tmp_path, runs):
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_xspace(runs)))
    return TR.load(TR.find_xplane(str(tmp_path)))


def _view(tr):
    off = TR.clock_offset(tr, H.MARK_KEY, [0])
    return H.RunView(window=None, mix={}, config={}, trace=tr,
                     trace_window=TR.window(0.0, 0.010, off))


def test_engine_device_ms_reads_the_served_program(tmp_path):
    view = _view(_load(tmp_path, RUNS))
    assert view.trace_window == pytest.approx((1.0 * MS, 11.0 * MS), abs=1)
    # the three runs that started in the window: 1.5, 0.5 and 1.5 ms
    assert H.reader("engine.device_ms")(view) == pytest.approx(3.5 / 3)
    # runs x time per run is the served program's share of busy time
    lo, hi = view.trace_window
    assert TR.busy_s(view.trace, lo, hi) == pytest.approx(2.5e-3)


def test_engine_device_ms_is_silent_without_the_served_program(tmp_path):
    """A program that names its served module otherwise, or a run without
    a trace, reads nothing and raises nothing."""
    view = _view(_load(tmp_path, [r for r in RUNS if r[0] != 2]))
    assert H.reader("engine.device_ms")(view) is None
    assert H.reader("engine.device_ms")(
        H.RunView(window=None, mix={}, config={})) is None


def test_untraced_run_records_no_span_events(monkeypatch, capsys):
    """``--trace 0`` turns no event record on: the program's spans keep
    their aggregates only."""
    from repro import obs

    from bench.tests.test_correct import _cell, _run

    cell = _cell("mq2008-deeptilebars.letor")
    dep = H.Deployment(cell.config, 2**33 + 19)
    obs.reset()
    line = _run(monkeypatch, (cell, dep), capsys)
    assert line["correct"], line["checks"]
    spans = obs.span_stats()
    assert obs.events() == []           # spans ran, none was recorded
    assert spans["engine.score"].count >= line["attempted"]
    assert spans["frontend.form"].count > 0
    assert np.isfinite(line["metrics"]["p50_ms"]["value"])
