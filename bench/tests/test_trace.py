"""The trace reduction, on a small recorded trace of known layout.

The trace is an XSpace written here as text and serialised by the
profiler's own converter, so it is read back through the same
``ProfileData`` path a chip run's ``.xplane.pb`` takes.  One TPU plane,
a window of 10 ms (host clock 1,000,000 .. 11,000,000 ns):

    device ops   [1.0, 3.0) fusion.1   [2.0, 4.0) gather.7 (overlaps)
                 [6.0, 6.05) fusion.1  [8.0, 10.0) custom-call.3
                 [12.0, 13.0) probe op, after the window
    modules      [0.9, 1.0) jit_bench_clock_mark, read 0 on the host clock
                 once it had finished (so host 0 is trace 1 ms)
                 [1.0, 4.0) jit__score   [12.0, 12.6), [12.6, 13.0)
                 jit_bench_lookup_probe (two runs)
    host         a line of spans, which the reduction does not read
    in flight    (the run's own record) [1, 4.5), [8, 10.8)
"""
import numpy as np
import pytest

from bench import harness as H
from bench import trace as TR

MS = 1_000_000   # ns


def _event(meta, start_ms, end_ms):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_ms * 1e9)}"
            f" duration_ps: {int((end_ms - start_ms) * 1e9)} }}")


XSPACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_event(1, 1.0, 3.0)} {_event(2, 2.0, 4.0)} {_event(1, 6.0, 6.05)}
    {_event(3, 8.0, 10.0)} {_event(1, 12.0, 13.0)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_event(6, 0.9, 1.0)} {_event(4, 1.0, 4.0)} {_event(5, 12.0, 12.6)}
    {_event(5, 12.6, 13.0)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "gather.7" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "custom-call.3" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit__score(1)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit_bench_lookup_probe(9)" }} }}
  event_metadata {{ key: 6 value {{ id: 6 name: "jit_bench_clock_mark(2)" }} }}
}}
planes {{
  id: 2 name: "/device:TPU:0 SparseCore 0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_event(1, 0.0, 20.0)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "not.a.chip" }} }}
}}
planes {{
  id: 3 name: "/host:CPU"
  lines {{ id: 2 name: "seine-frontend" timestamp_ns: 0
    {_event(3, 1.0, 5.0)} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "frontend.batch" }} }}
}}
"""


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return TR.load(TR.find_xplane(str(d.parent.parent.parent)))


def test_planes_and_window(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    off = TR.clock_offset(recorded, H.MARK_KEY, [0])
    assert off == pytest.approx(1.0 * MS, abs=1)
    assert TR.window(0.0, 0.010, off) == pytest.approx((1.0 * MS, 11.0 * MS),
                                                       abs=1)


def test_window_from_clock_marks_when_its_span_was_dropped(tmp_path):
    """The window is placed by the clock mark's device runs alone, the
    median over them; a trace without one run per host reading places no
    window."""
    from jax.profiler import ProfileData

    text = XSPACE.replace(_event(6, 0.9, 1.0), " ".join(
        _event(6, t - 0.1, t) for t in (2.0, 5.0, 9.0)))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    tr = TR.load(TR.find_xplane(str(tmp_path)))
    # ends 2, 5, 9 ms read 1, 3.5, 8 ms on the host: offsets 1, 1.5, 1 ms
    off = TR.clock_offset(tr, H.MARK_KEY, [1 * MS, 3.5 * MS, 8 * MS])
    assert off == pytest.approx(1.0 * MS, abs=1)
    assert TR.window(0.001, 0.010, off) == pytest.approx(
        (2.0 * MS, 12.0 * MS), abs=1)
    with pytest.raises(ValueError):
        TR.clock_offset(tr, H.MARK_KEY, [1 * MS])
    with pytest.raises(ValueError):
        TR.clock_offset(tr, "no_such_program", [])


def test_busy_is_the_union_inside_the_window(recorded):
    lo, hi = TR.window(0.0, 0.010, 1.0 * MS)
    # [1, 4) merged from two overlapping ops, [6, 6.05), [8, 10)
    assert TR.busy_s(recorded, lo, hi) == pytest.approx(5.05e-3)


def test_module_time_finds_the_probe_by_name(recorded):
    secs, runs = TR.module_time(recorded, H.PROBE_KEY)
    assert runs == 2 and secs == pytest.approx(1.0e-3)


def test_top_ops_are_clipped_to_the_window(recorded):
    lo, hi = TR.window(0.0, 0.010, 1.0 * MS)
    top = dict(TR.top_ops(recorded, lo, hi))
    assert top == pytest.approx({"fusion.1": 2.05e-3, "gather.7": 2.0e-3,
                                 "custom-call.3": 2.0e-3})


def test_idle_gaps_are_labelled_by_the_host_span(recorded):
    lo, hi = TR.window(0.0, 0.010, 1.0 * MS)
    flight = [(1.0 * MS, 4.5 * MS), (8.0 * MS, 10.8 * MS)]
    gaps = dict(TR.idle_gaps(recorded, lo, hi, flight, min_ns=0.1 * MS))
    # [4, 6): midpoint 5.0 after the first request's answer; [6.05, 8):
    # nothing in flight; [10, 11): midpoint 10.5 with a request in flight
    assert gaps == pytest.approx({"no_request_in_flight": 3.95e-3,
                                  "request_in_flight": 1.0e-3})
    busy = TR.busy_s(recorded, lo, hi)
    assert sum(gaps.values()) + busy == pytest.approx((hi - lo) / 1e9)
    assert dict(TR.idle_gaps(recorded, lo, hi, min_ns=0.1 * MS)) == \
        pytest.approx({"no_request_in_flight": 4.95e-3})
    # gaps under the threshold are summed apart
    fine = dict(TR.idle_gaps(recorded, lo, hi, flight, min_ns=1.5 * MS))
    assert fine == pytest.approx({"no_request_in_flight": 3.95e-3,
                                  "gaps_under_1500us": 1.0e-3})


def test_readers_on_the_recorded_trace(recorded):
    lo, hi = TR.window(0.0, 0.010, 1.0 * MS)
    cfg = {"n_segments": 20, "functions": ["f"] * 9, "dtype": "float32"}
    q = np.array([3, 4, -1, -1])
    view = H.RunView(window=None, mix={}, config=cfg, trace=recorded,
                     trace_window=(lo, hi), probe_requests=[(q, 10),
                                                            (q, 5)],
                     peaks={"hbm_bytes_per_s": 819e9})
    idle = H.reader("device.idle_pct")(view)
    assert idle == pytest.approx(100 * (1 - 5.05e-3 / 10e-3))
    assert H.reader("lookup.ms")(view) == pytest.approx(0.5)
    need = (2 * 10 + 2 * 5) * 20 * 9 * 8
    roof = H.reader("lookup_roofline")(view)
    assert roof == pytest.approx(100 * need / (1.0e-3 * 819e9), rel=1e-5)


def test_readers_return_nothing_without_a_trace():
    view = H.RunView(window=None, mix={}, config={})
    for name in ("device.idle_pct", "lookup.ms", "lookup_roofline"):
        assert H.reader(name)(view) is None
