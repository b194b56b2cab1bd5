"""The engine seam: a configuration with ``"engine": "noindex"`` serves
``NoIndexEngine`` through the same frontend and window, and ``correct``
holds it to the same reference; faults planted in its timed path fail the
run; and ``load_cell`` refuses a provider or an engine it does not know.

The run is ``bench/run.py``'s own ``main`` with the look for a chip
skipped, as in ``test_correct.py``, on a small copy of
``mq2008-deeptilebars`` with its limits.
"""
import json
import os
import shutil

import jax.numpy as jnp
import pytest

import bench.run as R
from bench import harness as H
from bench.tests.test_correct import _cell


@pytest.fixture(scope="module")
def world():
    cell = _cell("mq2008-deeptilebars.letor")
    cell.config["engine"] = "noindex"
    dep = H.Deployment(cell.config, 2**33 + 23)
    return cell, dep


def _run(monkeypatch, world, capsys) -> dict:
    from repro.serving import NoIndexEngine

    cell, dep = world
    old = dep.engine
    # a fresh engine per run, so a planted fault is traced afresh
    dep.engine = NoIndexEngine(old.builder, old.index, old.tokens, old.segs,
                               cell.config["ranker"], dep.params)
    monkeypatch.setattr(H, "load_cell", lambda name: cell)
    monkeypatch.setattr(H, "setup_jax", lambda config: None)
    monkeypatch.setattr(H, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(H, "peaks", lambda kind: {"hbm_bytes_per_s": 1.0})
    monkeypatch.setattr(H, "Deployment", lambda config, seed, log: dep)
    monkeypatch.setattr(dep, "release", lambda: None)
    assert R.main(["--workload", "tiny", "--seed", "2147483671",
                   "--seconds", "1", "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    return line


def test_noindex_deployment_holds_no_index(world):
    cell, dep = world
    assert dep.engine_kind == "noindex" and dep.index is None
    assert type(dep.engine).__name__ == "NoIndexEngine"
    assert isinstance(dep.engine.index, H.DocStats)
    assert dep.engine.tokens.shape == dep.corpus.tokens.shape
    assert dep.max_uniq == dep.corpus.tokens.shape[1]


def test_noindex_sound_run_is_correct(monkeypatch, world, capsys):
    """Correct against the reference, and the probe is the engine's
    on-the-fly function under a module name of its own, never the
    lookup's."""
    import jax
    from jax._src import dispatch

    traced = []

    def on(event, secs, **kw):
        if event == dispatch.JAXPR_TRACE_EVENT:
            traced.append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        line = _run(monkeypatch, world, capsys)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 30
    assert set(line["checks"]) == {"lost", "score_gap", "score_rms", "m_rms"}
    assert H.INTERACTION_PROBE_KEY in traced
    assert H.PROBE_KEY not in traced and "seine_score" not in traced


def test_noindex_altered_answer_fails(monkeypatch, world, capsys):
    from repro.serving import engine as E

    orig = E.NoIndexEngine.score
    monkeypatch.setattr(E.NoIndexEngine, "score",
                        lambda self, q, d: orig(self, q, d).at[3].multiply(
                            1.001))
    line = _run(monkeypatch, world, capsys)
    assert not line["correct"]
    assert line["checks"]["score_gap"]["value"] > \
        line["checks"]["score_gap"]["limit"]


def test_noindex_half_the_candidates_left_out_fails(monkeypatch, world,
                                                    capsys):
    from repro.serving import engine as E

    orig = E.NoIndexEngine.score

    def half(self, q, d):
        s = orig(self, q, d)
        n = s.shape[0] // 2
        return s.at[n:].set(jnp.mean(s[:n]))
    monkeypatch.setattr(E.NoIndexEngine, "score", half)
    assert not _run(monkeypatch, world, capsys)["correct"]


def test_noindex_altered_interaction_value_fails(monkeypatch, world, capsys):
    """A value of a function the ranker does not read, altered where the
    on-the-fly path computes M: only the probe's M can see it."""
    from repro.core.builder import IndexBuilder

    orig = IndexBuilder.make_qd_fn
    k = world[0].config["functions"].index("dot")

    def make_qd_fn(self):
        fn = orig(self)
        return lambda q, t, s: fn(q, t, s).at[..., k].multiply(1.001)
    monkeypatch.setattr(IndexBuilder, "make_qd_fn", make_qd_fn)
    line = _run(monkeypatch, world, capsys)
    assert not line["correct"]
    assert line["checks"]["m_rms"]["value"] > line["checks"]["m_rms"]["limit"]


def _checkout_with(tmp_path, **config_over) -> str:
    """A checkout whose one cell's configuration is changed."""
    root = tmp_path / "checkout"
    shutil.copytree(H.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(H.ROOT, "BENCHMARK.json"), root)
    conf = json.loads((root / "BENCHMARK.json").read_text())["configs"][1]
    path = root / conf["file"]
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    **config_over)))
    return str(root)


@pytest.mark.parametrize("over, what", [
    ({"provider": "word2vec"}, "no provider 'word2vec'"),
    ({"engine": "brute"}, "unknown engine 'brute'"),
])
def test_load_cell_refuses_an_unknown_seam(tmp_path, over, what):
    root = _checkout_with(tmp_path, **over)
    with pytest.raises(ValueError, match=what):
        H.load_cell("mq2008-deeptilebars.letor", root=root)


def test_load_cell_takes_a_known_engine(tmp_path):
    root = _checkout_with(tmp_path, engine="noindex")
    cell = H.load_cell("mq2008-deeptilebars.letor", root=root)
    assert cell.config["engine"] == "noindex"
    assert "engine" not in H.load_cell("mq2008-deeptilebars.letor").config
