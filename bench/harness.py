"""The benchmark's machinery: cells by name, the deployment, the open-loop
window, the lookup probe, the comparison with the reference, the result.

Whatever belongs to one configuration, traffic mix or per-layer metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     a deployment (its ``file`` entry)
    bench/providers/<provider>.py   its embedding provider (the
                                    configuration's ``"provider"``): the
                                    provider's weights, the program's
                                    provider object, the plain reference
    bench/rankers/<ranker>.py       the plain reference of its ranker
    bench/traffic/<traffic>.json    a mix, read by ``bench/traffic.py``
    bench/metrics/<metric>.py       one per-layer reader: ``read(run)``

A provider file may also name ``CONTROL_PRECISION``, the precision below
the one its encoder states, at which the control computes its part
(:func:`control`), and ``flops(config, n_tokens)``, the operations it
needs to encode one document (``bench/roofline.py:encoder_flops``).
A configuration whose ``reduced`` is not empty states its cut in its file
(:func:`check_cut`).

A configuration's ``"engine"`` (default ``"seine"``) chooses what serves:
``"seine"`` builds the index and serves ``SeineEngine``; ``"noindex"``
builds no index and serves ``NoIndexEngine``, which computes M per request
from the documents' tokens.

From the program the benchmark takes the system under test (the provider,
the index builder, the engine and ``ServingFrontend``), its spans and
counters.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from . import corpus as corpus_mod
from . import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
PROBE_KEY = "bench_lookup_probe"   # the probe's module name in the trace
INTERACTION_PROBE_KEY = "bench_interaction_probe"   # noindex's probe
MARK_KEY = "bench_clock_mark"      # the clock mark's module name
ENGINES = ("seine", "noindex")


class NoChip(RuntimeError):
    """JAX found no accelerator this benchmark knows, or too few chips."""


# -- cells by name ---------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A Python file under ``bench/`` by path (names may hold dots)."""
    name = "bench_file_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(os.path.join(root, conf["file"]))
    check_cut(conf, config)
    check_seams(config, root)
    mix = _load_json(os.path.join(root, "bench", "traffic",
                                  w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _for_cell(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _for_cell(m, name, names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer)


def check_cut(conf: dict, config: dict) -> None:
    """Refuse a configuration whose cut is not stated key by key, before
    any device work.  ``conf`` is its entry in ``BENCHMARK.json``; each key
    its ``reduced`` lists is a key of the configuration's file, which gives
    the source's value for it under ``published``, a value that differs
    from the file's, and under ``chips_per_layer`` over how many chips each
    layer of the stated deployment is divided (the chip holds one share of
    a layer; the layers left out lie on further chips).  An empty
    ``reduced`` needs neither key."""
    name = conf["name"]
    published = config.get("published", {})
    for k in conf["reduced"]:
        if k not in config:
            raise ValueError(f"configuration {name!r}: reduced key {k!r} "
                             f"is not a key of its file")
        if k not in published:
            raise ValueError(f"configuration {name!r}: reduced key {k!r} "
                             f"has no published value")
        if published[k] == config[k]:
            raise ValueError(f"configuration {name!r}: reduced key {k!r} "
                             f"holds its published value {config[k]!r}")
    cpl = config.get("chips_per_layer")
    if conf["reduced"] and (type(cpl) is not int or cpl < 1):
        raise ValueError(f"configuration {name!r}: a cut needs "
                         f"chips_per_layer, an integer >= 1 (has {cpl!r})")


def check_seams(config: dict, root: str = ROOT) -> None:
    """Refuse a configuration whose provider has no file under
    ``bench/providers/`` or whose engine is not one of :data:`ENGINES`,
    before any device work."""
    name = config.get("name")
    have = sorted(f[:-3] for f in os.listdir(os.path.join(
        root, "bench", "providers")) if f.endswith(".py"))
    if config.get("provider") not in have:
        raise ValueError(f"configuration {name!r}: no provider "
                         f"{config.get('provider')!r} under bench/providers/ "
                         f"(have {have})")
    engine = config.get("engine", "seine")
    if engine not in ENGINES:
        raise ValueError(f"configuration {name!r}: unknown engine "
                         f"{engine!r} (have {list(ENGINES)})")


def reader(metric: str):
    return load_module(os.path.join(BENCH, "metrics", metric + ".py")).read


def ranker(name: str):
    return load_module(os.path.join(BENCH, "rankers", name + ".py"))


def provider(name: str):
    return load_module(os.path.join(BENCH, "providers", name + ".py"))


# -- the device ------------------------------------------------------------

def device_info(chips: int) -> dict:
    """platform, kind and count of the chips JAX holds; raises NoChip on
    anything but enough TPUs of a kind ``bench/peaks.json`` lists."""
    import jax

    devs = jax.devices()
    peaks = _load_json(os.path.join(BENCH, "peaks.json"))
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d.platform!r})")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX holds {len(devs)}")
    if d.device_kind not in peaks:
        raise NoChip(f"device kind {d.device_kind!r} is not in "
                     f"bench/peaks.json ({sorted(peaks)})")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks(kind: str) -> dict:
    return _load_json(os.path.join(BENCH, "peaks.json"))[kind]


def setup_jax(config: dict) -> None:
    """Compile cache in the checkout (``repro.use_compile_cache``), every
    program cached, and the matrix precision the configuration states."""
    import jax

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro

    repro.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])


class CompileCounter:
    """Backend compiles, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.n = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on(name, secs, **_):
            if name == event:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on)


# -- the deployment ----------------------------------------------------------

def key_seed(seed: int, stream: int) -> int:
    """A 32-bit key for ``(seed, stream)``: a JAX key holds 32 bits of a
    seed, and seeds here are larger."""
    ss = np.random.SeedSequence(corpus_mod.seed_words(seed, stream))
    return int(ss.generate_state(1)[0])


def make_weights(config: dict, seed: int):
    """Interaction weights and ranker weights, on the device in one jitted
    call from the seed, in the layouts the program takes."""
    import jax
    import jax.numpy as jnp

    d, n_b = config["embed_dim"], config["n_segments"]
    rk = ranker(config["ranker"])

    @jax.jit
    def make(key):
        k = jax.random.split(key, 6)
        ip = {"a": jax.random.normal(k[0], (d,)) / jnp.sqrt(d),
              "b": 0.1 * jax.random.normal(k[1], ()),
              "mlp": {"w": [jax.random.normal(k[2], (d, 32)) / jnp.sqrt(d),
                            jax.random.normal(k[3], (32, 1)) / jnp.sqrt(32.)],
                      "b": [0.1 * jax.random.normal(k[4], (32,)),
                            jnp.zeros((1,))]}}
        return ip, rk.init(k[5], n_b)

    return make(jax.random.key(key_seed(seed, 11)))


@dataclasses.dataclass
class DocStats:
    """What ``make_qmeta`` reads of an index, and nothing else: the
    ``index`` a ``NoIndexEngine`` is given, which holds no values."""
    idf: object                 # (|v|,) float32
    doc_len: object             # (n_docs,) float32
    seg_len: object             # (n_docs, n_b) float32
    functions: tuple

    @property
    def avg_doc_len(self):
        import jax.numpy as jnp
        return jnp.mean(self.doc_len)


class Deployment:
    """One configuration at one seed: corpus, weights, provider, engine
    (with the index it serves from, for ``"seine"``)."""

    def __init__(self, config: dict, seed: int, log=None):
        import jax.numpy as jnp

        from repro.configs.base import SeineConfig
        from repro.core import IndexBuilder
        from repro.core.vocab import Vocabulary

        log = log or (lambda *a, **k: None)
        self.config, self.seed = config, seed
        self.engine_kind = config.get("engine", "seine")
        t = time.perf_counter()
        self.corpus = c = corpus_mod.generate(config, seed)
        log("corpus", s=time.perf_counter() - t,
            tokens=int((c.tokens >= 0).sum()))
        t = time.perf_counter()
        v = c.vocab_size
        prov = provider(config["provider"])
        self.provider_weights = prov.weights(config, seed, v)
        self.ip, self.params = make_weights(config, seed)
        self.functions = tuple(config["functions"])
        cfg = SeineConfig(
            name=config["name"], n_segments=config["n_segments"],
            embed_dim=config["embed_dim"],
            sigma_index=config["sigma_index"], functions=self.functions,
            n_docs=config["n_docs"], n_queries=config["n_queries"],
            avg_doc_len=config["avg_doc_len"], n_topics=config["n_topics"],
            provider=config["provider"])
        ident = np.arange(v, dtype=np.int32)
        vocab = Vocabulary(raw_to_slot=ident, slot_to_raw=ident,
                           idf=c.idf, n_docs=c.tokens.shape[0])
        builder = IndexBuilder(cfg, vocab,
                               prov.program(config, self.provider_weights, v),
                               ip=self.ip, functions=self.functions)
        if self.engine_kind == "seine":
            from repro.serving import SeineEngine

            self.index = builder.build_partitioned(
                c.tokens, c.segs, config["shards"],
                batch_size=config["build_batch"])
            self.max_uniq = min(c.tokens.shape[1], 512)  # builder's default
            self.engine = SeineEngine(self.index, config["ranker"],
                                      self.params)
            self.nnz = int(self.index.nnz)
        else:
            from repro.core.build_pipeline import compute_doc_seg_lengths
            from repro.serving import NoIndexEngine

            self.index = None
            doc_len, seg_len = compute_doc_seg_lengths(
                c.tokens, c.segs, config["n_segments"])
            stats = DocStats(jnp.asarray(c.idf), jnp.asarray(doc_len),
                             jnp.asarray(seg_len), self.functions)
            # M on the fly holds every term a document has: no cap
            self.max_uniq = c.tokens.shape[1]
            self.engine = NoIndexEngine(builder, stats, c.tokens, c.segs,
                                        config["ranker"], self.params)
            self.nnz = 0
        log("build", s=time.perf_counter() - t, engine=self.engine_kind,
            nnz=self.nnz)

    def frontend(self, mix: dict):
        from repro.serving import ServingFrontend

        fe = self.config["frontend"]
        return ServingFrontend(
            self.engine, max_batch=fe["max_batch"],
            batch_timeout_ms=fe["batch_timeout_ms"],
            batch_pad=mix["batch_pad"],
            coalesce=fe["coalesce"], cache_tiles=fe["cache_tiles"])

    def release(self) -> None:
        """Drop the index and engine (with the tokens a ``NoIndexEngine``
        holds on the device): the reference runs without them."""
        self.engine = self.index = None
        gc.collect()


def warm_up(dep: Deployment, mix: dict, seed: int) -> None:
    """Serve requests of the mix's own shapes through a frontend of the
    cell's settings, so every program the window runs is compiled."""
    n = 2 * dep.config["frontend"]["max_batch"]
    reqs = traffic.schedule(mix, dep.corpus, seed, n / mix["rate_rps"],
                            stream=4)
    with dep.frontend(mix) as fe:
        for f in [fe.submit(r.terms, r.docs) for r in reqs]:
            f.result()


# -- the window ------------------------------------------------------------

@dataclasses.dataclass
class Window:
    seconds: float
    due: np.ndarray          # absolute due times (perf_counter seconds)
    submitted: np.ndarray
    done: np.ndarray         # NaN where no answer came
    status: List[str]        # "ok" | "error" | "missing"
    scores: List[Optional[np.ndarray]]
    queue_ms: float          # mean admission-to-dequeue wait (ServeStats)
    compiles: int
    errors: List[str]

    start: float = 0.0
    drain_s: float = 60.0
    gc_pauses: List[tuple] = dataclasses.field(default_factory=list)
    host_stalls: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def served(self) -> np.ndarray:
        return np.array([s == "ok" for s in self.status])

    @property
    def latency_ms(self) -> np.ndarray:
        """Due to answer, per request.  A request not served (failed or
        never answered) counts as waiting until the drain's end."""
        end = self.start + self.seconds + self.drain_s
        lat = (self.done - self.due) * 1e3
        return np.where(self.served & np.isfinite(lat), lat,
                        (end - self.due) * 1e3)

    @property
    def late_ms(self) -> np.ndarray:
        return (self.submitted - self.due) * 1e3


class ClockMark:
    """A tiny program on the device, run and waited for just before a
    traced window opens.  Its run in the trace's device plane, beside the
    host clock's reading once it finished, ties the trace's clock to the
    host's without any host event (the host tracer drops those under
    load).  Built, and compiled, in set-up."""

    def __init__(self):
        import jax
        import jax.numpy as jnp

        def bench_clock_mark(x):
            return x + 1

        self._run = jax.jit(bench_clock_mark)
        self._x = jnp.zeros((8, 128), jnp.float32)
        self._run(self._x).block_until_ready()
        self.host_ns: List[int] = []      # readings while tracing

    def __call__(self) -> None:
        self._run(self._x).block_until_ready()
        self.host_ns.append(time.perf_counter_ns())


class GcPauses:
    """The collector's pauses on the host clock, while installed."""

    def __init__(self):
        self.pauses: List[tuple] = []       # (start_s, seconds, generation)
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class Heartbeat:
    """A thread that wakes every ``every_s`` and notes each wake-up that
    came ``over_s`` or more late, as ``(time.time(), seconds late)``: the
    whole process (or the interpreter's lock) stalled there, not only the
    device or the frontend's worker."""

    def __init__(self, every_s: float = 0.005, over_s: float = 0.05):
        self.every_s, self.over_s = every_s, over_s
        self.stalls: List[tuple] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="bench-heartbeat")

    def _beat(self) -> None:
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(self.every_s)
            late = time.perf_counter() - t - self.every_s
            if late >= self.over_s:
                self.stalls.append((time.time() - late, late))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


def open_loop(dep: Deployment, mix: dict, reqs: list, seconds: float, *,
              mark: ClockMark = None, counter: CompileCounter = None,
              drain_s: float = 60.0) -> Window:
    """Submit each request at its due time, never waiting on answers; then
    wait for every answer until ``drain_s`` past the window's close.
    ``mark`` (a traced window) runs just before the window opens."""
    n = len(reqs)
    submitted = np.full(n, np.nan)
    done = np.full(n, np.nan)
    futs = []
    c0 = counter.n if counter else 0
    fe = dep.frontend(mix)
    gcp, beat = GcPauses(), Heartbeat()

    def wait_until(t: float) -> None:
        while (left := t - time.perf_counter()) > 0:
            time.sleep(left)

    try:
        with gcp, beat:
            if mark is not None:
                mark()
            start = time.perf_counter()
            for i, r in enumerate(reqs):
                wait_until(start + r.due_s)
                submitted[i] = time.perf_counter()
                f = fe.submit(r.terms, r.docs)
                f.add_done_callback(
                    lambda _, i=i: done.__setitem__(i, time.perf_counter()))
                futs.append(f)
            wait_until(start + seconds)
            concurrent.futures.wait(
                futs, timeout=max(0.0, start + seconds + drain_s
                                  - time.perf_counter()))
    finally:
        # close() drains every admitted request; with an answer missing it
        # would wait for ever, so the (daemon) worker is left to the exit
        if all(f.done() for f in futs):
            fe.close()
    status, scores, errors = [], [], []
    for f in futs:
        if not f.done():
            status.append("missing")
            scores.append(None)
            continue
        e = f.exception()
        if e is None:
            status.append("ok")
            scores.append(f.result())
        else:
            status.append("error")
            scores.append(None)
            errors.append(f"{type(e).__name__}: {e}")
    return Window(seconds, start + np.array([r.due_s for r in reqs]),
                  submitted, done, status, scores,
                  fe.stats.queue_ms_per_request,
                  (counter.n - c0) if counter else 0, errors, start,
                  drain_s, [p for p in gcp.pauses
                            if start <= p[0] < start + seconds],
                  beat.stalls)


# -- the probe and the comparison --------------------------------------------

def padded(docs: np.ndarray, pad: int) -> np.ndarray:
    """Candidates padded as the frontend pads them (``batch_pad``)."""
    n = docs.shape[0]
    if pad > 0 and n % pad:
        docs = np.concatenate([docs, np.full(-(-n // pad) * pad - n,
                                             docs[0], docs.dtype)])
    return docs


def probe(dep: Deployment, mix: dict, reqs: list, idx: List[int]) -> list:
    """M of each checked request at its served (padded) shape, from what
    the engine computes it with.

    ``"seine"``: the benchmark's own jit of ``PartitionedIndex.qd_matrix``;
    its module in the trace is named after ``PROBE_KEY``, and its device
    time gives the lookup's per-request time and roofline share.
    ``"noindex"``: the engine's on-the-fly function (its builder's
    ``make_qd_fn``) over the tokens it holds, under
    ``INTERACTION_PROBE_KEY``, so no lookup metric reads its time."""
    import jax

    if dep.engine_kind == "seine":
        def bench_lookup_probe(index, q, d):
            return index.qd_matrix(q, d)

        run = functools.partial(jax.jit(bench_lookup_probe), dep.index)
    else:
        eng = dep.engine
        qd_fn = eng.builder.make_qd_fn()

        def bench_interaction_probe(tokens, segs, q, d):
            return qd_fn(q, tokens[d], segs[d])

        run = functools.partial(jax.jit(bench_interaction_probe),
                                eng.tokens, eng.segs)
    outs = [run(reqs[i].terms, padded(reqs[i].docs, mix["batch_pad"]))
            for i in idx]
    return [np.asarray(m)[:len(reqs[i].docs)] for m, i in zip(outs, idx)]


def doc_tokens(corpus, reqs: list, idx: List[int]) -> list:
    """Per checked request, the real token count of each of its real
    candidates (``RunView.probe_doc_tokens``): the work an encoder has to
    do for it, without the candidates the frontend pads a request with or
    the positions a document is padded with."""
    return [(corpus.tokens[reqs[i].docs] >= 0).sum(1) for i in idx]


def reference(dep: Deployment, reqs: list, idx: List[int],
              precision: str = "highest",
              provider_precision: str = None) -> list:
    """The plain reference's ``(M, scores)`` for the checked requests: the
    interactions and the ranker at ``precision``, the provider's part at
    ``provider_precision`` (by default the same)."""
    import jax.numpy as jnp

    from . import reference as ref

    c, cfg = dep.corpus, dep.config
    prov = provider(cfg["provider"])
    prov_precision = provider_precision or precision

    def embed(weights, tokens, segs, _precision):
        return prov.reference(weights, tokens, segs, cfg, prov_precision)

    return ref.score_requests(
        [(reqs[i].terms, reqs[i].docs) for i in idx], c.tokens, c.segs,
        embed, dep.provider_weights, jnp.asarray(c.idf), dep.ip, dep.params,
        ranker(cfg["ranker"]).score, n_b=cfg["n_segments"],
        functions=dep.functions, sigma=cfg["sigma_index"],
        max_uniq=dep.max_uniq, precision=precision)


def control(dep: Deployment, reqs: list, idx: List[int]) -> list:
    """The control's ``(M, scores)``: the reference with the interactions
    and the ranker at ``high`` (three bfloat16 passes, the precision below
    the configurations' float32 at ``highest``), and the provider's part at
    the precision below the one the provider states: its file's
    ``CONTROL_PRECISION``, or ``high`` where it names none."""
    prov = provider(dep.config["provider"])
    return reference(dep, reqs, idx, "high",
                     getattr(prov, "CONTROL_PRECISION", "high"))


def gaps(served: list, got_m: list, want: list) -> Dict[str, float]:
    """The numbers a run can compare; a mix's ``checks`` name those it
    does.

    ``score_gap``: the widest gap between a served score and the
    reference's, over the largest reference score (all checked requests);
    ``score_rms``: the root mean square of those gaps over the reference
    scores' root mean square: rounding that is off in every score, as a
    lower precision is, moves it where a few scores' noise does not;
    ``m_gap``: per atomic function the widest gap between the probe's M and
    the reference's over the largest reference value, the worst function;
    ``m_rms``: per atomic function the root mean square of those gaps over
    that of the reference values, and of these the root mean square over
    the functions: rounding off in every value of the functions a lower
    precision reaches moves it, where one function's own rounding (a
    distance factored in float32) moves it less.
    """
    s = np.concatenate([np.asarray(x, np.float64) for x in served])
    r = np.concatenate([w[1].astype(np.float64) for w in want])
    score_gap = np.abs(s - r).max() / max(np.abs(r).max(), 1e-30)
    score_rms = np.sqrt(np.mean((s - r) ** 2)) / max(
        np.sqrt(np.mean(r ** 2)), 1e-30)
    per_f = m_by_function(got_m, want)
    return {"score_gap": float(score_gap), "score_rms": float(score_rms),
            "m_gap": max(f["gap"] for f in per_f),
            "m_rms": float(np.sqrt(np.mean([f["rms"] ** 2
                                            for f in per_f])))}


def m_by_function(got_m: list, want: list) -> List[dict]:
    """Per atomic function: ``gap`` and ``rms`` as :func:`gaps` takes
    them, the largest reference value, and where the widest gap lies
    (``at``: the flat index over ``(doc, term, segment)`` rows of the
    sample, with the probe's and the reference's value there)."""
    m = np.concatenate([x.astype(np.float64) for x in got_m])
    rm = np.concatenate([w[0].astype(np.float64) for w in want])
    m, rm = m.reshape(-1, m.shape[-1]), rm.reshape(-1, m.shape[-1])
    d = np.abs(m - rm)
    out = []
    for f in range(m.shape[1]):
        top = max(float(np.abs(rm[:, f]).max()), 1e-30)
        at = int(d[:, f].argmax())
        out.append({"gap": float(d[at, f]) / top,
                    "rms": float(np.sqrt(np.mean(d[:, f] ** 2)) / max(
                        np.sqrt(np.mean(rm[:, f] ** 2)), 1e-30)),
                    "ref_max": top, "at": at, "got": float(m[at, f]),
                    "ref": float(rm[at, f])})
    return out


def compare(win: Window, reqs: list, idx: List[int], got_m: list,
            want: list, limits: dict) -> dict:
    """Every number the mix's ``limits`` name, beside its limit; ``lost``
    counts answers that never came, errors, and answers of the wrong
    length."""
    bad = sum(1 for st, sc, r in zip(win.status, win.scores, reqs)
              if st in ("missing", "error")
              or (st == "ok" and np.shape(sc) != (len(r.docs),)))
    out = {"lost": {"value": bad, "limit": 0}}
    served = [win.scores[i] for i in idx]
    if any(np.shape(x) != (len(reqs[i].docs),) for x, i in zip(served, idx)):
        g = {k: float("inf") for k in limits}
    else:
        g = gaps(served, got_m, want)
    for k in limits:
        out[k] = {"value": g[k], "limit": limits[k]}
    return out


def is_correct(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


# -- per-layer readers' view of a run ----------------------------------------

@dataclasses.dataclass
class RunView:
    """What a metric's reader reads: the window, set-up and memory, the
    reduced trace, the probe's requests with the real tokens of each of
    their candidates, and the chip's peaks."""
    window: Window
    mix: dict
    config: dict
    setup_s: float = None
    memory_peak_bytes: int = None
    trace: object = None                 # bench.trace.Trace
    trace_window: tuple = None           # (start_ns, end_ns)
    probe_requests: list = None          # [(terms, n_candidates)]
    probe_doc_tokens: list = None        # [(n_candidates,) real tokens]
    peaks: dict = None


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, tuple], device: dict,
                checks: dict, breakdown: dict = None) -> str:
    """The last line of standard output; ``checks`` comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": u}
                       for k, (v, u) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)

