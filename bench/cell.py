"""One benchmark cell: its files, the engine it builds, warm-up and the
open-loop window.

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  Both are found by name; nothing here
names a cell.

The engine is built through the program's own constructors
(``ModelEmbedder``, ``EdgeRAGIndex``, ``GeneratorModel``, ``RAGEngine``)
with the configuration's values, and weights the benchmark made.  Spans
(``jax.profiler.TraceAnnotation`` plus host-clock intervals) wrap the
program's methods on the instances: a method that no longer exists fails
the run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# warm-up requests go through search_batch this many at a time; the most
# queries the probe is compiled for
MAX_BATCH = 8
# clusters resolved together when the cache is filled
FILL_BATCH = 64


def load_spec(workload: str, root: Path = ROOT):
    """(benchmark, workload entry, configuration, traffic) for a cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = json.loads(
        (root / "bench" / "configs" / f"{cell['config']}.json").read_text())
    traffic = load_traffic(cell["traffic"], root / "bench" / "traffic")
    return bench, cell, config, traffic


def load_traffic(name: str, directory: Path) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class Spans:
    """Host-clock intervals by name, each also a profiler annotation."""

    def __init__(self):
        self.by_name: Dict[str, List[tuple]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.by_name.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(e - s for s, e in self.by_name.get(name, ()))

    def since(self, t: float) -> "Spans":
        """The spans that started at or after ``t``."""
        out = Spans()
        for name, ivs in self.by_name.items():
            kept = [(s, e) for s, e in ivs if s >= t]
            if kept:
                out.by_name[name] = kept
        return out


def wrap(obj, method: str, spans: Spans, name: str,
         after: Optional[Callable] = None):
    """Replace ``obj.method`` on the instance by a spanned call.
    ``after(result, *args)`` sees each call's result."""
    inner = getattr(obj, method)             # AttributeError: run fails

    def spanned(*args, **kw):
        with spans.span(name):
            out = inner(*args, **kw)
        if after is not None:
            after(out, *args)
        return out
    setattr(obj, method, spanned)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    config: dict
    corpus: object
    embedder: object
    index: object
    engine: object
    enc_params: object
    gen_params: object
    spans: Spans
    setup: Dict[str, float]
    probes: List[tuple] = dataclasses.field(default_factory=list)
    slabs: List[object] = dataclasses.field(default_factory=list)
    scores: List[tuple] = dataclasses.field(default_factory=list)
    slab_work: List[tuple] = dataclasses.field(default_factory=list)
    regen_tokens: List[tuple] = dataclasses.field(default_factory=list)
    top_logits: List[list] = dataclasses.field(default_factory=list)
    thresholds: List[float] = dataclasses.field(default_factory=list)

    def clear_records(self):
        for recorded in (self.probes, self.slabs, self.scores,
                         self.slab_work, self.regen_tokens, self.top_logits,
                         self.thresholds):
            recorded.clear()
        self.spans.by_name.clear()


def _timed(setup: Dict[str, float], name: str):
    @contextlib.contextmanager
    def t():
        t0 = time.perf_counter()
        yield
        setup[name] = time.perf_counter() - t0
    return t()


def seed_ints(seed: int, n: int) -> List[int]:
    """``n`` 31-bit integers drawn from any whole-number seed."""
    return [int(x) for x in
            np.random.default_rng([seed, 7]).integers(0, 2**31 - 1, size=n)]


def build(config: dict, seed: int, n_queries: int) -> Cell:
    """Corpus and queries from the seed, weights on the device, the index
    built by the program (its encoder embeds the corpus), the engine."""
    import jax
    from repro.core import EdgeCostModel, EdgeRAGIndex
    from repro.data.embedder import ModelEmbedder
    from repro.serving.engine import GeneratorModel, RAGEngine
    from bench import models
    from bench.traffic.generate import make_corpus, topic_words

    setup: Dict[str, float] = {}
    enc_m, gen_m = config["encoder"], config["generator"]
    k_enc, k_gen = seed_ints(seed, 2)
    with _timed(setup, "corpus_s"):
        corpus = make_corpus(config, n_queries, seed)
    enc_arch, gen_arch = models.arch(enc_m), models.arch(gen_m)
    with _timed(setup, "weights_s"):
        topics = [topic_words(t) for t in range(config["topics"])]
        enc_params = enc_arch.init_weights(
            enc_m, k_enc, models.topic_rows(enc_m["vocab_size"], topics))
        gen_params = gen_arch.init_weights(gen_m, k_gen)
        jax.block_until_ready((enc_params, gen_params))
    embedder = ModelEmbedder(enc_arch.program_config(enc_m),
                             params=enc_params, max_len=enc_m["max_len"])
    with _timed(setup, "embed_corpus_s"):
        corpus_emb = embedder(corpus.texts)
    cost = EdgeCostModel()
    with _timed(setup, "index_build_s"):
        index = EdgeRAGIndex(embedder.dim, embedder, corpus.get_chunks, cost,
                             slo_s=config["slo_s"],
                             cache_bytes=config["cache_bytes"],
                             storage_codec=config["storage_codec"])
        index.build(np.arange(len(corpus.texts)), corpus.texts,
                    nlist=config["nlist"], seed=k_enc,
                    embeddings=corpus_emb)
    del corpus_emb
    gen = GeneratorModel(gen_arch.program_config(gen_m), params=gen_params,
                         max_prompt=gen_m["max_prompt"])
    engine = RAGEngine(index, gen, cost_model=cost, k=config["k"],
                       nprobe=config["nprobe"],
                       max_new_tokens=gen_m["max_new_tokens"])
    return Cell(config=config, corpus=corpus, embedder=embedder,
                index=index, engine=engine, enc_params=enc_params,
                gen_params=gen_params, spans=Spans(), setup=setup)


def instrument(cell: Cell):
    """Spans around S1-S4 and the regeneration encoder, and the counts the
    per-layer readers and the correctness check take from the window: the
    probe's inputs and choices, each batch's packed slab (the slab top-k's
    inputs) and scores, the Alg. 3 threshold after each batch, and the
    largest logit of each generation step (its served token's logit, kept
    on the device until the window closes)."""
    spans, engine, index = cell.spans, cell.engine, cell.index

    def probed(job, *_):
        cell.probes.append((np.array(job.query_embs),
                            [list(p) for p in job.state.plan.probed_per_q]))

    def slab(job, *_):
        sizes = [index.clusters[c].size for c in range(index.nlist)]
        per_q = [sum(sizes[c] for c in p) for p in job.state.plan.probed_per_q]
        unique = sum(sizes[c] for c in {c for p in job.state.plan.probed_per_q
                                        for c in p})
        cell.slab_work.append((per_q, unique))

    def scored(out, *_):
        cell.scores.append((np.array(out[0]), np.array(out[1])))
        cell.thresholds.append(index.threshold.threshold)

    wrap(engine, "stage_plan", spans, "s1.plan", probed)
    wrap(engine, "stage_fetch", spans, "s2.fetch")
    wrap(engine, "stage_score", spans, "s3.score", slab)
    wrap(engine, "stage_decode", spans, "s4.generate")
    wrap(index, "search_finish", spans, "s3.search_finish", scored)
    wrap(index.resolver, "pack_slab", spans, "s3.pack",
         lambda layout, *_: cell.slabs.append(layout))
    gen = engine.generator
    prefill, decode = gen._prefill, gen._decode

    def prefilled(params, batch, caches):
        logits, caches = prefill(params, batch, caches)
        cell.top_logits.append([logits.max(axis=-1)])
        return logits, caches

    def decoded(params, tok, caches, n):
        logits, caches = decode(params, tok, caches, n)
        cell.top_logits[-1].append(logits.max(axis=-1))
        return logits, caches
    gen._prefill, gen._decode = prefilled, decoded
    encoder = index.embed_fn
    max_len = cell.config["encoder"]["max_len"]

    def regen(texts):
        t0 = time.perf_counter()
        with spans.span("s2.regen_encode"):
            out = encoder(texts)
        cell.regen_tokens.extend((t0, min(max_len, len(t.split()) + 1))
                                 for t in texts)
        return out
    index.embed_fn = regen


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def persist_compiles(on: bool):
    """Write compiled programs to the persistent cache (``on``) or keep them
    in this process only.  Model programs persist, so a cell's second run
    finds them; programs that a slab's row count shapes are never written,
    so no run finds a program an earlier run compiled in its window."""
    import jax
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0 if on else 1e9)
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def warm_up(cell: Cell, traffic: dict, seed: int):
    """Compile every model shape the window uses, then serve the mix's
    ``warmup_requests`` from another query stream of the seed through
    ``search_batch``, which fills the cache as the traffic would, and one
    ``answer_batch`` of the mix's batch size.  ``prepare_window`` does
    what depends on the window's own requests."""
    from repro.kernels.ivf_topk.ops import topk_ip
    from bench.traffic.generate import make_queries
    emb, gen, index = cell.embedder, cell.engine.generator, cell.index
    with _timed(cell.setup, "warm_models_s"), persist_compiles(True):
        for b in range(emb.MAX_BATCH.bit_length()):
            emb(["warm up"] * (1 << b))
        gen.generate("warm up", cell.config["generator"]["max_new_tokens"])
        for q in range(1, max(MAX_BATCH, int(traffic["max_batch"])) + 1):
            np.asarray(topk_ip(index.centroids, np.zeros((q, emb.dim),
                                                         np.float32),
                               cell.config["nprobe"])[1])
    n, nb = int(traffic["warmup_requests"]), int(traffic["max_batch"])
    warm, _ = make_queries(cell.config, cell.corpus.sizes, max(n, nb), seed,
                           1)
    with _timed(cell.setup, "warm_requests_s"), persist_compiles(False):
        k, nprobe = cell.config["k"], cell.config["nprobe"]
        for s in range(0, n, MAX_BATCH):
            index.search_batch(emb(warm[s:min(n, s + MAX_BATCH)]), k, nprobe)
        batch = warm[:nb]
        cell.engine.answer_batch(batch, emb(batch), cell.corpus.get_chunks)


def window_probes(cell: Cell, texts: List[str], groups: List[tuple]):
    """Per batch of ``groups``, the clusters each of its queries probes:
    the batch's queries embedded as the window embeds them and probed by
    the program's own probe.  Reads the index and changes nothing."""
    emb, index, nprobe = cell.embedder, cell.index, cell.config["nprobe"]
    return [index._probe(np.asarray(emb(texts[i:j]), np.float32), nprobe)
            for i, j, _ in groups]


def prepare_window(cell: Cell, traffic: dict, texts: List[str],
                   groups: List[tuple]):
    """Set-up that depends on the window's own requests: with the mix's
    ``fill_cache``, every cluster they probe is in the cache
    (``fill_cache``), and every batch's slab shape is compiled
    (``warm_slabs``)."""
    probes = window_probes(cell, texts, groups)
    if traffic.get("fill_cache"):
        fill_cache(cell, probes)
    warm_slabs(cell, probes)


def fill_cache(cell: Cell, probes: List[list]):
    """Put every cluster the window probes that the index does not store
    into the cache, through the program's own S1 and S2
    (``search_begin`` with a plan of one cluster per query, then
    ``search_fetch``, which regenerates each missing cluster with the
    encoder and admits it).  S3 is not run, so the Alg. 3 threshold
    controller sees no warm-up request: the window starts with the cache
    a deployment whose memory holds the index has, and the controller at
    rest (set-up phase ``fill_cache_s``)."""
    index, k = cell.index, cell.config["k"]
    cids = sorted({c for batch in probes for probed in batch for c in probed
                   if not index.clusters[c].stored})
    with _timed(cell.setup, "fill_cache_s"):
        for s in range(0, len(cids), FILL_BATCH):
            chunk = cids[s:s + FILL_BATCH]
            plan = index.resolver.plan([[c] for c in chunk])
            index.search_fetch(index.search_begin(
                np.zeros((len(chunk), index.dim), np.float32), k, 1,
                plan=plan))
    missing = [c for c in cids if c not in index.cache]
    if missing:
        raise RuntimeError(f"fill_cache: clusters {missing[:8]} not cached")
    cell.setup["filled_clusters"] = len(cids)


def warm_slabs(cell: Cell, probes: List[list]):
    """Compile the slab top-k for every batch shape of a window, so that
    nothing compiles inside it (set-up phase ``warm_slabs_s``, with the
    number of shapes ``slab_shapes``).  Every cluster of a fp32 index
    packs into one fp32 segment: one launch of (rows, dim) slab rows, the
    rows of the batch's unique probed clusters, against (queries, dim)
    queries per batch.  The programs stay out of the persistent cache, as
    the slab shapes differ from seed to seed."""
    from repro.kernels.slab_topk.ops import slab_topk
    dim, k = cell.embedder.dim, cell.config["k"]
    sizes = [c.size for c in cell.index.clusters]
    shapes = sorted({(sum(sizes[c] for c in {c for p in batch for c in p}),
                      len(batch)) for batch in probes})
    with _timed(cell.setup, "warm_slabs_s"), persist_compiles(False):
        for rows, nq in shapes:
            np.asarray(slab_topk(np.zeros((rows, dim), np.float32),
                                 np.zeros((nq, dim), np.float32),
                                 np.zeros((nq, rows), np.int32), k)[0])
    cell.setup["slab_shapes"] = len(shapes)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    query: str
    due: float                      # perf_counter seconds
    done: float
    batch: int                      # which answer_batch call answered it
    embedding: np.ndarray           # the query embedding the loop made
    response: object                # the program's RAGResponse
    score: Optional[np.ndarray] = None   # its passages' scores (S3)
    top_logits: Optional[np.ndarray] = None  # its tokens' logits (S4)


def serve_window(cell: Cell, texts: List[str], arrivals: np.ndarray,
                 groups: List[tuple], trace_until: Optional[float] = None,
                 on_trace_end: Optional[Callable] = None):
    """Open loop: the batches of ``groups`` (``batch_groups``) in order,
    each sent as one query-embedding call and one ``answer_batch`` once it
    is ready and the one before it is answered; while the next is not
    ready the loop sleeps.  Each request is timed from its own arrival.
    Returns the served requests in arrival order, the window start and the
    sleep overshoots (how late the loop woke for a batch)."""
    spans, emb, engine = cell.spans, cell.embedder, cell.engine
    get_chunks = cell.corpus.get_chunks
    served: List[Optional[Served]] = [None] * len(arrivals)
    late: List[float] = []
    t0 = time.perf_counter()
    due = t0 + np.asarray(arrivals)
    for b, (i, j, ready) in enumerate(groups):
        while True:
            now = time.perf_counter()
            if trace_until is not None and now >= t0 + trace_until:
                on_trace_end()
                trace_until = None
            if t0 + ready <= now:
                break
            time.sleep(t0 + ready - now)
            late.append(time.perf_counter() - t0 - ready)
        batch = texts[i:j]
        with spans.span("bench.batch"):
            with spans.span("embed.query"):
                embs = emb(batch)
            out = engine.answer_batch(batch, embs, get_chunks)
        done = time.perf_counter()
        for r in range(i, j):
            served[r] = Served(texts[r], float(due[r]), done, b,
                               embs[r - i], out[r - i])
    if trace_until is not None:
        on_trace_end()
    return served, t0, late
