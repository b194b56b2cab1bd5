"""``correct`` against the plain reference: a sound run passes, the control
and every fault the cells can have fail.

The run is ``bench/run.py``'s own ``main`` with the look for a chip
skipped, on a deployment of the cells' widths (n_b = 20, 9 functions,
embed_dim 128) at a size a CPU test holds, with each cell's own limits.
Faults are planted in the program underneath the timed path: an answer
altered where the engine produces it, answers paired with the wrong
request, half the candidates left out with the mean of the rest in their
place, and an M value altered in the lookup (which the ranker does not
read: only the comparison of the probe's M can see it).  The control is
the reference at ``high`` put in the program's place.
"""
import json
import types

import jax.numpy as jnp
import numpy as np
import pytest

import bench.run as R
from bench import harness as H
from bench import traffic

SIZE = dict(n_docs=160, n_queries=12, max_len=192)


def _cell(name: str, **mix_over) -> H.Cell:
    cell = H.load_cell(name)
    cfg = dict(cell.config, **SIZE)
    mix = dict(cell.mix, rate_rps=30.0, check_requests=12, **mix_over)
    if mix["depth"]["kind"] == "fixed":
        mix["depth"] = {"kind": "fixed", "n": 64}
        mix["batch_pad"] = 64
    return H.Cell("tiny", 1, cfg, mix, cell.end_to_end, cell.per_layer)


@pytest.fixture(scope="module", params=["mq2007-knrm.rerank1k",
                                        "mq2008-deeptilebars.letor"])
def world(request):
    """One deployment per cell kind, shared by the runs of this module;
    each run rebuilds the engine, so a planted fault is traced afresh."""
    cell = _cell(request.param)
    dep = H.Deployment(cell.config, 2**33 + 17)
    return cell, dep


def _run(monkeypatch, world, capsys) -> dict:
    from repro.serving import SeineEngine

    cell, dep = world
    dep.engine = SeineEngine(dep.index, cell.config["ranker"], dep.params)
    monkeypatch.setattr(H, "load_cell", lambda name: cell)
    monkeypatch.setattr(H, "setup_jax", lambda config: None)
    monkeypatch.setattr(H, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(H, "peaks", lambda kind: {"hbm_bytes_per_s": 1.0})
    monkeypatch.setattr(H, "Deployment", lambda config, seed, log: dep)
    monkeypatch.setattr(dep, "release", lambda: None)
    assert R.main(["--workload", "tiny", "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0"]) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith(
        f"check {list(cell.mix['checks'])[-1]}")
    return line


def test_sound_run_is_correct(monkeypatch, world, capsys):
    line = _run(monkeypatch, world, capsys)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 30
    cell, _ = world
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"p50_ms", "peak_hbm_gb", "setup_s"} <= set(line["metrics"])


def test_control_fails(world):
    """The control (the reference at ``high``: the hash provider names no
    ``CONTROL_PRECISION``) in the program's place reads over a limit of
    the served scores and over that of the probe's M."""
    cell, dep = world
    reqs = traffic.schedule(cell.mix, dep.corpus, 3, 0.5)
    idx = list(range(len(reqs)))
    want = H.reference(dep, reqs, idx, "highest")
    ctrl = H.control(dep, reqs, idx)
    for (m0, s0), (m1, s1) in zip(H.reference(dep, reqs, idx, "high"),
                                  ctrl):
        assert np.array_equal(m0, m1) and np.array_equal(s0, s1)
    got = H.gaps([c[1] for c in ctrl], [c[0] for c in ctrl], want)
    lim = cell.mix["checks"]
    assert got["score_rms"] > lim["score_rms"]
    assert got["m_rms"] > lim["m_rms"]


def test_control_takes_the_providers_precision(monkeypatch, world):
    """A provider's ``CONTROL_PRECISION`` reaches its ``reference`` in the
    control; the interactions and the ranker stay at ``high``."""
    from bench import reference as ref

    cell, dep = world
    hp = H.provider("hash")
    seen = []

    def reference(weights, tokens, segs, config, precision="highest"):
        seen.append(precision)
        return hp.reference(weights, tokens, segs, config, precision)

    toy = types.SimpleNamespace(CONTROL_PRECISION=ref.BELOW_BF16,
                                reference=reference)
    monkeypatch.setattr(H, "provider", lambda name: toy)
    reqs = traffic.schedule(cell.mix, dep.corpus, 5, 0.2)[:2]
    ctrl = H.control(dep, reqs, [0, 1])
    assert seen and set(seen) == {ref.BELOW_BF16}
    both = H.reference(dep, reqs, [0, 1], "high", ref.BELOW_BF16)
    assert all(np.array_equal(a[1], b[1]) for a, b in zip(ctrl, both))
    high = H.reference(dep, reqs, [0, 1], "high")
    assert not all(np.array_equal(a[0], b[0]) for a, b in zip(ctrl, high))


def _patch_score(monkeypatch, fn):
    from repro.serving import engine as E

    orig = E.SeineEngine.score
    monkeypatch.setattr(E.SeineEngine, "score",
                        lambda self, q, d: fn(orig(self, q, d)))


def test_altered_answer_fails(monkeypatch, world, capsys):
    _patch_score(monkeypatch, lambda s: s.at[3].multiply(1.001))
    line = _run(monkeypatch, world, capsys)
    assert not line["correct"]
    assert line["checks"]["score_gap"]["value"] > \
        line["checks"]["score_gap"]["limit"]


def test_half_the_candidates_left_out_fails(monkeypatch, world, capsys):
    def half(s):
        n = s.shape[0] // 2
        return s.at[n:].set(jnp.mean(s[:n]))
    _patch_score(monkeypatch, half)
    assert not _run(monkeypatch, world, capsys)["correct"]


def test_answers_paired_with_the_wrong_request_fail(monkeypatch, world,
                                                    capsys):
    from repro.serving import frontend as F

    orig = F.ServingFrontend._serve

    def swapped(self, batch):
        if len(batch) >= 2:
            batch[0].future, batch[1].future = (batch[1].future,
                                                batch[0].future)
        return orig(self, batch)
    monkeypatch.setattr(F.ServingFrontend, "_serve", swapped)
    cell, dep = world
    # bursts of simultaneous requests, so batches hold more than one
    monkeypatch.setattr(traffic, "arrivals",
                        lambda spec, rate, seconds, rng: np.repeat(
                            np.arange(0, seconds, 0.1), 4)[:int(
                                rate * seconds)])
    line = _run(monkeypatch, world, capsys)
    assert not line["correct"]


def test_altered_lookup_value_fails(monkeypatch, world, capsys):
    from repro.dist import partition as P

    orig = P.PartitionedIndex.qd_matrix
    k = world[0].config["functions"].index("dot")

    def qd(self, *a, **kw):
        m = orig(self, *a, **kw)
        return m.at[..., k].multiply(1.001)
    monkeypatch.setattr(P.PartitionedIndex, "qd_matrix", qd)
    line = _run(monkeypatch, world, capsys)
    assert not line["correct"]
    assert line["checks"]["m_rms"]["value"] > line["checks"]["m_rms"]["limit"]
