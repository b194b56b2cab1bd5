"""BENCHMARK.json against the benchmark's contract, discovery of cells,
configurations, mixes and metrics by name, the result line, the traffic
generator and the lookup's byte count."""
import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import harness as H
from bench import roofline

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(H.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(bench)) <= 64 * 1024


def _check_configs(bench, root):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        # a cut is stated key by key: each reduced key is in the file, with
        # a published value that differs, and the chips that share a layer
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        H.check_cut(c, cfg)
        assert os.path.isfile(os.path.join(H.BENCH, "rankers",
                                           cfg["ranker"] + ".py"))
        assert any(w["config"] == c["name"] for w in bench["workloads"])


def test_configs(bench):
    _check_configs(bench, H.ROOT)


# a one-chip share of Moonlight-16B-A3B's layers, as a later configuration
# would state it: 1 dense + 4 MoE layers, 8 of 64 experts, 1/8 of the rows
CUT = {"n_layers": (5, 27), "n_experts": (8, 64),
       "vocab_size": (20480, 163840)}


def _cut_checkout(tmp_path, bench, fault=None):
    """A checkout whose ``BENCHMARK.json`` adds a configuration that lists
    the keys of :data:`CUT` in ``reduced``, and a cell of it; ``fault``
    leaves one part of the stated cut out."""
    root = tmp_path / "checkout"
    shutil.copytree(H.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = bench["configs"][1]
    with open(os.path.join(H.ROOT, base["file"])) as f:
        cfg = json.load(f)
    cfg = dict(cfg, name="cut", chips_per_layer=8,
               published={k: p for k, (_, p) in CUT.items()},
               **{k: v for k, (v, _) in CUT.items()})
    if fault == "key_not_in_file":
        del cfg["n_experts"]
    elif fault == "published_equals_file":
        cfg["published"] = dict(cfg["published"], vocab_size=20480)
    elif fault == "no_chips_per_layer":
        del cfg["chips_per_layer"]
    (root / "bench" / "configs" / "cut.json").write_text(json.dumps(cfg))
    new = dict(bench)
    new["configs"] = bench["configs"] + [dict(
        base, name="cut", file="bench/configs/cut.json",
        reduced=list(CUT))]
    new["workloads"] = bench["workloads"] + [
        {"name": "cut.letor", "config": "cut", "traffic": "letor",
         "chips": 1, "why": "a later cell of a cut configuration"}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    return new, str(root)


def test_configs_accept_a_stated_cut(tmp_path, bench):
    new, root = _cut_checkout(tmp_path, bench)
    _check_configs(new, root)
    cell = H.load_cell("cut.letor", root=root)
    assert cell.config["chips_per_layer"] == 8
    assert cell.config["published"]["n_experts"] == 64


@pytest.mark.parametrize("fault", ["key_not_in_file",
                                   "published_equals_file",
                                   "no_chips_per_layer"])
def test_configs_refuse_an_unstated_cut(tmp_path, bench, fault):
    new, root = _cut_checkout(tmp_path, bench, fault)
    with pytest.raises(ValueError):
        _check_configs(new, root)
    with pytest.raises(ValueError):
        H.load_cell("cut.letor", root=root)


def test_every_config_names_its_provider_and_engine(bench):
    """Each configuration's provider has its file under
    ``bench/providers/`` (weights, program, reference), and its engine,
    where it names one, is one the harness serves."""
    for c in bench["configs"]:
        with open(os.path.join(H.ROOT, c["file"])) as f:
            cfg = json.load(f)
        prov = H.provider(cfg["provider"])
        for part in ("weights", "program", "reference"):
            assert callable(getattr(prov, part)), (cfg["provider"], part)
        assert cfg.get("engine", "seine") in H.ENGINES


def test_workloads(bench):
    configs = {c["name"] for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        mix = H.load_cell(w["name"]).mix
        assert mix["limit_ms"] > 0 and mix["rate_rps"] > 0
        assert mix["arrivals"]["kind"] in ("poisson", "onoff")
        assert mix["queries"]["kind"] in ("uniform", "zipf")
        assert set(mix["checks"]) <= {"score_gap", "score_rms", "m_gap",
                                      "m_rms"}
        d = mix["depth"]
        assert (d["n"] if d["kind"] == "fixed" else d["max"]) \
            <= mix["batch_pad"], "one score shape must serve every request"


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    per_layer = bench["per_layer"]
    names = list(e2e) + [m["name"] for m in per_layer]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in per_layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for m in bench["end_to_end"] + per_layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(H.reader(m["name"]))
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        cell = H.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_a_new_cell_is_found_by_name(tmp_path, bench):
    """A later cell needs only entries and files of its own."""
    root = tmp_path / "checkout"
    shutil.copytree(H.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = dict(H.load_cell(bench["workloads"][0]["name"]).mix, rate_rps=7.0)
    (root / "bench" / "traffic" / "slow.json").write_text(json.dumps(mix))
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [
        {"name": "mq2007-knrm.slow", "config": "mq2007-knrm",
         "traffic": "slow", "chips": 1, "why": "a later cell"}]
    new["end_to_end"] = [dict(m, workloads=m["workloads"] + [
        "mq2007-knrm.slow"]) if "workloads" in m else m
        for m in bench["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = H.load_cell("mq2007-knrm.slow", root=str(root))
    assert cell.mix["rate_rps"] == 7.0 and cell.config["ranker"] == "knrm"
    assert {m["name"] for m in cell.end_to_end} >= {"p50_ms", "setup_s"}


def test_result_line_schema():
    checks = {"lost": {"value": 0, "limit": 0},
              "score_gap": {"value": 1e-7, "limit": 1e-5}}
    line = H.result_line(True, 10, 0, {"p50_ms": (1.5, "ms")},
                         {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1, "memory_peak_bytes": 5},
                         checks, {"device_ops": [], "idle_gaps": []})
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    assert out["metrics"]["p50_ms"] == {"value": 1.5, "unit": "ms"}
    assert H.is_correct(checks)
    assert not H.is_correct(dict(checks, lost={"value": 1, "limit": 0}))
    assert not H.is_correct(
        {"m_gap": {"value": float("inf"), "limit": 1.0}})


def test_lookup_bytes_count_real_pairs_only():
    q = np.array([5, 9, 2, -1, -1, -1, -1, -1])
    # 3 real terms x 1,000 candidates, 20 x 9 f32 values read + f32 written
    assert roofline.lookup_bytes([(q, 1000)], 20, 9, "float32") \
        == 3 * 1000 * 180 * 8
    assert roofline.lookup_bytes([(q, 1000), (q[:0], 64)], 20, 9,
                                 "float32") == 3 * 1000 * 180 * 8


def test_doc_tokens_count_real_tokens_of_real_candidates():
    """``probe_doc_tokens``: per checked request, the real tokens of each
    candidate it sent, not the candidates the frontend pads it with
    (``batch_pad``) nor the positions each document is padded with."""
    from bench.traffic import Request

    tokens = np.full((6, 16), -1, np.int32)
    for d, n in enumerate([3, 16, 0, 7, 1, 12]):
        tokens[d, :n] = np.arange(n) + 2
    corpus = type("Corpus", (), {"tokens": tokens})
    q = np.array([2, 3, -1, -1], np.int32)
    reqs = [Request(0.0, 0, q, np.array([1, 3, 4], np.int32)),
            Request(0.1, 1, q, np.array([5], np.int32))]
    assert len(H.padded(reqs[0].docs, 8)) == 8
    got = H.doc_tokens(corpus, reqs, [1, 0])
    assert [n.tolist() for n in got] == [[12], [16, 7, 1]]


def test_encoder_flops_count_real_documents_one_by_one():
    """A provider's ``flops`` summed per real document, at its own length
    (a length-squared term tells one document of 2n tokens from two of n);
    ``None`` for ``hash``, which names no ``flops``."""
    def flops(config, n):
        return config["per_token"] * n + n * n

    toy = type("Toy", (), {"flops": staticmethod(flops)})
    cfg = {"per_token": 10}
    assert roofline.encoder_flops(toy, cfg, [np.array([3, 5]),
                                             np.array([2])]) \
        == (30 + 9) + (50 + 25) + (20 + 4)
    assert roofline.encoder_flops(toy, cfg, [np.array([8])]) \
        != roofline.encoder_flops(toy, cfg, [np.array([4, 4])])
    assert roofline.encoder_flops(toy, cfg, []) == 0
    assert roofline.encoder_flops(H.provider("hash"), cfg,
                                  [np.array([3])]) is None


def test_peaks_table():
    p = H.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        H.peaks("TPU v9 imaginary")


def test_no_chip_is_refused():
    with pytest.raises(H.NoChip):
        H.device_info(1)


def _run_cli(cwd, env_extra=None):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mq2008-deeptilebars.letor", "--seed", "3000000017",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    out = _run_cli(H.ROOT)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_cli_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(H.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(H.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(str(tmp_path))
    assert out.returncode != 0 and out.stdout == ""
