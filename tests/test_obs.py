"""repro.obs: registry semantics, span aggregation, exporter round-trips,
lifecycle instrumentation (build/shard/serve/fault), the serve-driver
``--metrics-out`` surface, the async checkpoint failure-injection story
and the <5% serve-loop overhead bound."""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax

from repro import obs
from repro.obs.metrics import Registry


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts and ends with a zeroed global registry so the
    lifecycle counters other suites bump never leak across tests."""
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_accumulates_and_labels(self):
        c = obs.counter("t_events_total", "help text")
        c.inc()
        c.inc(2.5)
        c.inc(shard="0")
        assert c.get() == 3.5
        assert c.get(shard="0") == 1.0
        assert obs.counter("t_events_total") is c       # get-or-create

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            obs.counter("t_neg_total").inc(-1)

    def test_kind_conflict_raises(self):
        obs.counter("t_kind")
        with pytest.raises(TypeError):
            obs.gauge("t_kind")

    def test_gauge_set_inc_dec(self):
        g = obs.gauge("t_depth")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.get() == 6.0

    def test_gauge_clear_drops_stale_labels(self):
        g = obs.gauge("t_per_shard")
        g.set(10, shard="0")
        g.set(20, shard="1")
        g.clear()
        g.set(30, shard="0")
        assert g.samples() == [((("shard", "0"),), 30.0)]

    def test_histogram_buckets_and_percentile(self):
        h = obs.histogram("t_lat_ms", buckets=(1.0, 10.0, 100.0))
        for v in (0.5, 5.0, 5.0, 50.0):
            h.observe(v)
        cell = h.cells[()]
        assert cell.counts == [1, 2, 1, 0]      # +Inf slot empty
        assert cell.count == 4
        assert cell.sum == pytest.approx(60.5)
        assert h.percentile(50) == 10.0          # bucket upper bound
        assert h.percentile(99) == 100.0

    def test_disabled_suppresses_all_recording(self):
        with obs.disabled():
            obs.counter("t_off_total").inc()
            obs.gauge("t_off").set(1)
            obs.histogram("t_off_ms").observe(1.0)
            with obs.span("t.off"):
                pass
        assert obs.counter("t_off_total").get() == 0.0
        assert obs.gauge("t_off").get() == 0.0
        assert not obs.histogram("t_off_ms").cells
        assert "t.off" not in obs.span_stats()
        assert obs.enabled()                     # restored on exit

    def test_registry_get_is_read_only(self):
        assert obs.REGISTRY.get("t_never_created") is None
        obs.counter("t_created_total").inc()
        assert obs.REGISTRY.get("t_created_total").get() == 1.0


class TestSpans:
    def test_span_aggregates_per_name(self):
        for _ in range(3):
            with obs.span("t.stage"):
                time.sleep(0.001)
        st = obs.span_stats()["t.stage"]
        assert st.count == 3
        assert st.total_s >= 0.003
        assert st.min_s <= st.last_s <= st.total_s

    def test_spans_nest(self):
        with obs.span("t.outer"):
            with obs.span("t.inner"):
                assert obs.trace.current_span() == "t.inner"
        stats = obs.span_stats()
        assert stats["t.outer"].count == 1
        assert stats["t.inner"].count == 1
        assert stats["t.outer"].total_s >= stats["t.inner"].total_s


class TestEventRecord:
    @pytest.fixture(autouse=True)
    def _record_off(self):
        yield
        obs.record_events(None)

    def test_off_by_default_and_records_nothing(self):
        with obs.span("t.unrecorded", size=3):
            pass
        assert obs.events() == []
        assert obs.span_stats()["t.unrecorded"].count == 1  # aggregate
        obs.record_events(4)
        obs.record_events(None)                 # off again: dropped
        with obs.span("t.unrecorded"):
            pass
        assert obs.events() == []

    def test_bounded_at_capacity(self):
        obs.record_events(3)
        for i in range(5):
            with obs.span("t.ring", i=i):
                pass
        evs = obs.events()
        assert [e.attrs["i"] for e in evs] == [2, 3, 4]   # the last 3
        with pytest.raises(ValueError, match="capacity"):
            obs.record_events(0)

    def test_times_are_perf_counter_ns(self):
        import threading

        obs.record_events(8)
        before = time.perf_counter_ns()
        with obs.span("t.outer"):
            with obs.span("t.inner") as attrs:
                time.sleep(0.002)
                attrs["size"] = 7               # filled in as learnt
        after = time.perf_counter_ns()
        inner, outer = obs.events()             # in order of exit
        assert (inner.name, outer.name) == ("t.inner", "t.outer")
        assert before <= outer.t0_ns <= inner.t0_ns
        assert inner.t1_ns - inner.t0_ns >= 2_000_000
        assert inner.t1_ns <= outer.t1_ns <= after
        assert inner.thread == threading.current_thread().name
        assert inner.attrs == {"size": 7} and outer.attrs == {}
        # the aggregate is the same interval, in seconds
        st = obs.span_stats()["t.inner"]
        assert st.last_s == pytest.approx(
            (inner.t1_ns - inner.t0_ns) / 1e9, abs=1e-9)

    def test_reset_clears_events_and_keeps_recording(self):
        obs.record_events(8)
        with obs.span("t.x"):
            pass
        obs.reset()
        assert obs.events() == []
        with obs.span("t.y"):
            pass
        assert [e.name for e in obs.events()] == ["t.y"]   # still on


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

class TestExport:
    def test_prometheus_round_trip(self):
        r = Registry()
        r.counter("rt_reqs_total", "requests").inc(7)
        r.gauge("rt_depth").set(2.5, queue="a b\"c\\d")   # escaping
        h = r.histogram("rt_ms", buckets=(1.0, 10.0))
        h.observe(0.5)
        h.observe(5.0)
        text = obs.to_prometheus(r, include_spans=False)
        back = obs.parse_prometheus(text)
        assert back["rt_reqs_total"][()] == 7.0
        assert back["rt_depth"][(("queue", 'a b"c\\d'),)] == 2.5
        # cumulative buckets + sum/count
        assert back["rt_ms_bucket"][(("le", "1"),)] == 1.0
        assert back["rt_ms_bucket"][(("le", "10"),)] == 2.0
        assert back["rt_ms_bucket"][(("le", "+Inf"),)] == 2.0
        assert back["rt_ms_sum"][()] == pytest.approx(5.5)
        assert back["rt_ms_count"][()] == 2.0

    def test_prometheus_includes_span_aggregates(self):
        with obs.span("t.export"):
            pass
        back = obs.parse_prometheus(obs.to_prometheus())
        key = (("span", "t.export"),)
        assert back["seine_span_count_total"][key] == 1.0
        assert back["seine_span_seconds_total"][key] >= 0.0

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            obs.parse_prometheus("!! not a sample line")

    def test_json_dump_and_write_metrics(self, tmp_path):
        obs.counter("t_dump_total").inc(3)
        with obs.span("t.dump"):
            pass
        p = tmp_path / "snap.json"
        snap = obs.dump(str(p))
        on_disk = json.loads(p.read_text())
        assert on_disk["metrics"]["t_dump_total"]["samples"][0]["value"] == 3
        assert on_disk["spans"]["t.dump"]["count"] == 1
        assert snap["metrics"].keys() == on_disk["metrics"].keys()
        prom = tmp_path / "snap.prom"
        obs.write_metrics(str(prom))
        assert "t_dump_total 3" in prom.read_text()


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------

class TestLog:
    def test_info_format_and_stderr(self, capsys):
        obs.get_logger("t.logger").info("hello", docs=3)
        err = capsys.readouterr().err
        assert "[t.logger] hello docs=3" in err

    def test_error_increments_counter(self, capsys):
        obs.get_logger("t.logger").error("boom", why="x")
        assert obs.counter("seine_log_errors_total").get(
            logger="t.logger") == 1.0
        assert "ERROR: boom" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# lifecycle instrumentation
# ---------------------------------------------------------------------------

def _make_engine(seine_world, **kw):
    from repro.retrievers import get_retriever
    from repro.serving import SeineEngine
    w = seine_world
    params = get_retriever("knrm").init(
        jax.random.key(0), w["cfg"].n_segments, w["index"].functions)
    return SeineEngine(w["index"], "knrm", params, **kw)


def _requests(seine_world, n=8, cand=32):
    from repro.data.batching import candidates_for_query
    w = seine_world
    rng = np.random.RandomState(0)
    return [(w["queries"][i % len(w["queries"])],
             candidates_for_query(w["ds"].qrels[i % len(w["queries"])],
                                  rng, cand))
            for i in range(n)]


class TestLifecycleInstrumentation:
    def test_build_counters_and_stage_spans(self, seine_world):
        w = seine_world
        w["builder"].build(w["toks"], w["segs"], batch_size=16)
        assert obs.counter("seine_build_docs_total").get() == \
            w["toks"].shape[0]
        assert obs.counter("seine_build_runs_total").get() > 0
        assert obs.gauge("seine_build_total_nnz").get() == w["index"].nnz
        spans = obs.span_stats()
        for name in ("build.stream_runs", "build.stage1.uniq",
                     "build.stage2.interact", "build.stage2b.compact",
                     "build.stage3.spill", "build.stage4.merge"):
            assert name in spans, name

    def test_partition_records_shard_balance(self, seine_world):
        from repro.dist.sharding import partition_index
        partition_index(seine_world["index"], 2)
        nnz = dict(obs.gauge("seine_shard_nnz").samples())
        assert set(nnz) == {(("shard", "0"),), (("shard", "1"),)}
        assert sum(nnz.values()) == seine_world["index"].nnz
        assert obs.gauge("seine_shard_count").get() == 2
        assert obs.gauge("seine_shard_skew_max_ratio").get() >= 1.0
        # re-partitioning to fewer shards must drop stale labels
        partition_index(seine_world["index"], 1)
        assert len(obs.gauge("seine_shard_nnz").samples()) == 1

    def test_serve_requests_and_sampled_lookup_stats(self, seine_world):
        from repro.serving import serve_batches
        engine = _make_engine(seine_world)
        # 30 candidates + pad bucket 16 -> padded to 32, so real pad waste
        reqs = _requests(seine_world, n=4, cand=30)
        out, stats = serve_batches(engine, reqs, batch_pad=16)
        assert len(out) == 4
        assert obs.counter("seine_serve_requests_total").get() == 4
        assert obs.counter("seine_engine_scores_total").get() == 4
        assert obs.histogram("seine_serve_latency_ms").cells[()].count == 4
        assert obs.span_stats()["engine.score"].count == 4
        # every request counted exactly, from the host arrays: valid
        # terms x real candidates (pads excluded), nothing sampled
        want = sum(int((np.asarray(q) >= 0).sum()) * len(d)
                   for q, d in reqs)
        assert want > 0
        assert obs.counter("seine_lookup_pairs_total").get() == want
        assert obs.REGISTRY.get("seine_lookup_found_ratio") is None
        assert obs.gauge("seine_index_nnz").get() == \
            seine_world["index"].nnz
        assert obs.gauge("seine_serve_pad_waste_ratio").get() > 0.0

    def test_frontend_batch_events_on_the_worker(self, seine_world):
        """One batch of the frontend's worker, recorded: its formation,
        then per request the engine's dispatch, the wait and the answer,
        nested in ``frontend.batch``, all on the ``seine-frontend``
        thread."""
        from repro.serving import ServingFrontend
        engine = _make_engine(seine_world)
        reqs = _requests(seine_world, n=2, cand=16)
        with ServingFrontend(engine, max_batch=2, batch_timeout_ms=10_000,
                             coalesce=False) as fe:
            fe.submit(*reqs[0]).result(timeout=300)      # warm: compile
            t_rec = time.perf_counter_ns()
            obs.record_events(64)
            try:
                futs = [fe.submit(q, d) for q, d in reqs]
                for f in futs:
                    f.result(timeout=300)
            finally:
                fe.close()              # the batch's spans have all exited
                evs = obs.events()
                obs.record_events(None)
        # the warm-up batch may still be closing its spans at t_rec
        worker = [e for e in evs if e.thread == "seine-frontend"
                  and e.t0_ns >= t_rec]
        assert [e.name for e in worker] == [
            "frontend.form", "engine.score", "engine.score",
            "frontend.wait", "frontend.answer", "frontend.wait",
            "frontend.answer", "frontend.batch"]
        form, batch = worker[0], worker[-1]
        assert form.attrs == {"size": 2}
        assert form.t1_ns <= batch.t0_ns
        for a, b in zip(worker[1:-1], worker[2:-1]):
            assert a.t1_ns <= b.t0_ns                    # in sequence
        assert all(batch.t0_ns <= e.t0_ns and e.t1_ns <= batch.t1_ns
                   for e in worker[1:-1])
        # counted from the host arrays the worker holds
        assert obs.counter("seine_lookup_pairs_total").get() == sum(
            int((np.asarray(q) >= 0).sum()) * len(d)
            for q, d in [reqs[0]] + reqs)

    def test_serve_batches_empty_request_short_circuits(self, seine_world):
        from repro.serving import serve_batches
        engine = _make_engine(seine_world)
        reqs = _requests(seine_world, n=1) + \
            [(seine_world["queries"][0], np.zeros(0, np.int32))]
        out, stats = serve_batches(engine, reqs, batch_pad=16)
        assert out[1].shape == (0,)
        assert out[1].dtype == np.float32
        assert stats.n_requests == 1            # degenerate not timed
        assert obs.counter("seine_serve_requests_total").get() == 2
        assert obs.counter(
            "seine_serve_degenerate_requests_total").get() == 1

    def test_heartbeat_and_straggler_gauges(self):
        from repro.dist.fault import Heartbeat, StragglerMonitor
        t = [0.0]
        hb = Heartbeat(deadline_s=10.0, clock=lambda: t[0])
        hb.beat(0)
        hb.beat(1)
        t[0] = 20.0
        hb.beat(1)
        assert hb.dead_ranks() == [0]
        assert obs.gauge("seine_heartbeat_age_seconds").get(
            rank="0") == 20.0
        assert obs.gauge("seine_heartbeat_dead_ranks").get() == 1
        mon = StragglerMonitor(tau=2.0, min_history=2)
        for _ in range(4):
            mon.record(0, 1.0)
        mon.record(1, 10.0)
        assert obs.counter("seine_straggler_flagged_total").get() == 1
        assert obs.gauge(
            "seine_straggler_median_step_seconds").get() == 1.0


class TestServedProgramNames:
    def test_interpret_lookup_holds_scopes_and_kernel_names(
            self, seine_world):
        """The served program is ``jit_seine_score``; its lowered text
        (the kernel path, in the Pallas interpreter) names its four parts
        and the position kernel, and retrieval names its window kernel —
        the names a device trace attributes operations by."""
        import re

        from repro.dist.sharding import partition_index

        def scopes(lowered):
            # every name-stack component of the program's op locations
            text = lowered.as_text(debug_info=True)
            return text, {part for loc in re.findall(r'loc\("([^"]*)"', text)
                          for part in loc.split("/")}

        pidx = partition_index(seine_world["index"], 2)
        engine = _make_engine(dict(seine_world, index=pidx))
        engine._lookup_impl = "interpret"          # the kernel path on CPU
        q = jax.numpy.asarray(seine_world["queries"][0])
        d = jax.numpy.arange(16, dtype=jax.numpy.int32)
        text, names = scopes(engine._score.lower(engine.params,
                                                 engine.index, q, d))
        assert "module @jit_seine_score" in text
        assert {"seine.lookup.ids", "seine.lookup.positions",
                "seine.lookup.gather", "seine.rank",
                "seine_csr_positions"} <= names
        _, names = scopes(engine._retrieve.lower(
            engine.params, engine.index, q, k=4, doc_block=None))
        assert "seine_window_rows" in names


# ---------------------------------------------------------------------------
# checkpoint failure injection (async writer must not fail silently)
# ---------------------------------------------------------------------------

class TestCkptFailureInjection:
    def test_async_index_save_failure_recovers_previous(
            self, tmp_path, monkeypatch, seine_world):
        import dataclasses

        from repro.ckpt import load_index, save_index, wait_async
        index = seine_world["index"]
        d = str(tmp_path / "index")
        save_index(d, index)                    # generation 1, clean
        gen1_values = np.asarray(index.values)

        # inject: the PUBLISH os.replace (dst == index dir) fails AFTER
        # the live index was moved aside — the exact crash window the
        # .old fallback exists for
        real_replace = os.replace

        def failing_replace(src, dst):
            if os.path.abspath(dst) == os.path.abspath(d):
                raise OSError("injected publish failure")
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", failing_replace)
        gen2 = dataclasses.replace(index, values=index.values * 2.0)
        save_index(d, gen2, async_write=True)
        with pytest.raises(OSError, match="injected publish failure"):
            wait_async()                        # surfaced, not swallowed
        monkeypatch.setattr(os, "replace", real_replace)

        assert obs.counter("seine_ckpt_write_errors_total").get() == 1.0
        assert obs.counter("seine_index_saves_total").get() == 1.0
        # generation 1 is recovered from the .old move-aside
        restored = load_index(d)
        np.testing.assert_array_equal(np.asarray(restored.values),
                                      gen1_values)

    def test_async_ckpt_write_failure_raises_on_join(
            self, tmp_path, monkeypatch):
        from repro.ckpt import save_checkpoint, wait_async

        def boom(*a, **kw):
            raise OSError("disk full (injected)")

        monkeypatch.setattr(np, "savez", boom)
        save_checkpoint(str(tmp_path / "ck"), 1, {"w": np.ones(3)},
                        async_write=True)
        with pytest.raises(OSError, match="disk full"):
            wait_async()
        assert obs.counter("seine_ckpt_write_errors_total").get() == 1.0
        assert obs.counter("seine_ckpt_saves_total").get() == 0.0

    def test_sync_save_still_raises_and_counts(self, tmp_path,
                                               monkeypatch):
        from repro.ckpt import save_checkpoint

        def boom(*a, **kw):
            raise OSError("injected")

        monkeypatch.setattr(np, "savez", boom)
        with pytest.raises(OSError):
            save_checkpoint(str(tmp_path / "ck"), 1, {"w": np.ones(3)})
        assert obs.counter("seine_ckpt_write_errors_total").get() == 1.0


# ---------------------------------------------------------------------------
# serve driver --metrics-out (the acceptance surface)
# ---------------------------------------------------------------------------

class TestServeDriverMetricsOut:
    @pytest.mark.slow
    def test_metrics_out_prometheus_covers_lifecycle(self, tmp_path,
                                                     monkeypatch):
        from repro.launch import serve as serve_mod
        out = tmp_path / "seine.prom"
        monkeypatch.setattr(sys, "argv", [
            "serve", "--partition", "term", "--shards", "2",
            "--n-queries", "4", "--candidates", "32", "--batch-pad", "16",
            "--metrics-out", str(out)])
        serve_mod.main()
        fams = obs.parse_prometheus(out.read_text())
        # build stage timings
        spans = fams["seine_span_seconds_total"]
        assert spans[(("span", "build.stage2.interact"),)] > 0
        assert spans[(("span", "build.stage4.merge"),)] > 0
        # per-shard nnz
        nnz = fams["seine_shard_nnz"]
        assert {k for (_, k), in nnz} == {"0", "1"}
        assert all(v > 0 for v in nnz.values())
        # (term, candidate) pairs served, counted on the host: 2 passes
        # x 4 requests x 32 candidates x the queries' valid terms
        pairs = fams["seine_lookup_pairs_total"][()]
        assert pairs > 0 and pairs % (2 * 32) == 0
        assert "seine_lookup_found_ratio" not in fams
        # serve latency histogram (2 serve_batches passes x 4 requests)
        assert fams["seine_serve_latency_ms_count"][()] == 8.0
        assert fams["seine_serve_latency_ms_bucket"][
            (("le", "+Inf"),)] == 8.0
        # heartbeat age
        assert fams["seine_heartbeat_age_seconds"][
            (("rank", "0"),)] >= 0.0

    @pytest.mark.slow
    def test_metrics_out_json_snapshot(self, tmp_path, monkeypatch):
        from repro.launch import serve as serve_mod
        out = tmp_path / "seine.json"
        monkeypatch.setattr(sys, "argv", [
            "serve", "--n-queries", "2", "--candidates", "16",
            "--metrics-out", str(out)])
        serve_mod.main()
        snap = json.loads(out.read_text())
        assert snap["metrics"]["seine_serve_requests_total"][
            "samples"][0]["value"] == 4
        assert "serve.request" in snap["spans"]


# ---------------------------------------------------------------------------
# overhead bound: instrumentation must stay <5% on the serve loop
# ---------------------------------------------------------------------------

class _SynthEngine:
    """Deterministic stand-in for SeineEngine in the overhead A/B: the
    same per-score obs surface (the ``engine.score`` span and the cached
    call counter), but a fixed numpy workload instead of an XLA dispatch.
    Async-dispatch jitter on a loaded machine is 10-20x the
    instrumentation cost, so an A/B over the real engine measures
    scheduler noise, not obs; pure host compute makes min-of-N converge
    to the actual delta."""

    def __init__(self, work_elems: int = 16_384):
        self._x = np.random.RandomState(0).rand(work_elems) \
            .astype(np.float32)
        self._scores_counter = obs.counter("seine_engine_scores_total",
                                           "engine.score calls")

    def score(self, q, docs):
        with obs.span("engine.score"):
            self._scores_counter.inc()
            np.sort(self._x)                # the "request": ~100s of us
            return np.zeros(np.asarray(docs).shape[0], np.float32)


class TestOverhead:
    def test_serve_loop_overhead_under_5_percent(self):
        import statistics

        from repro.serving import serve_batches
        # Bounds the ALWAYS-ON instrumentation on the serve loop: request
        # and pair counters, serve.request and engine.score spans,
        # latency histogram, engine score counter.  No metric runs a
        # device program, so nothing is left out of the A/B.
        #
        # Estimator: shared CI machines drift multiplicatively on ~100ms
        # scales, so raw min-of-N across arms measures load, not obs.
        # Instead each enabled run is PAIRED with an adjacent disabled
        # run (order alternating) and the window's median ratio is the
        # estimate; up to 3 windows, pass on the first clean one.  The
        # true cost is ~3us of ~850us/request (<0.5%), so a window
        # median beyond 1.05 is load spiking across every pair — retry —
        # while a real hot-path regression (a device sync, an O(n) scan)
        # shifts every pair in every window and still fails.
        engine = _SynthEngine(work_elems=131_072)
        reqs = [(np.arange(6, dtype=np.int32),
                 np.arange(32, dtype=np.int64))] * 16

        serve_batches(engine, reqs, batch_pad=32)       # warm both arms
        with obs.disabled():
            serve_batches(engine, reqs, batch_pad=32)

        def run_once():
            t0 = time.perf_counter()
            serve_batches(engine, reqs, batch_pad=32)
            return time.perf_counter() - t0

        medians = []
        for _ in range(3):
            ratios = []
            for i in range(11):
                if i % 2:
                    with obs.disabled():
                        off = run_once()
                    ratios.append(run_once() / off)
                else:
                    on = run_once()
                    with obs.disabled():
                        ratios.append(on / run_once())
            medians.append(statistics.median(ratios))
            if medians[-1] <= 1.05:
                break
        assert min(medians) <= 1.05, (
            f"obs overhead {min(medians) - 1:.1%} exceeds 5% in all "
            f"{len(medians)} windows (paired-median ratios: "
            f"{', '.join(f'{m:.3f}' for m in medians)})")
