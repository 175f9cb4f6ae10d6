"""Serving launcher: the EdgeRAG served path, end to end.

Builds the FiQA-shaped corpus of the paper's Table 2 (25,000 passages),
embeds it with gte-base-en-v1.5 and indexes it with EdgeRAG: k-means
clusters, Alg. 1 selective storage, Alg. 2/3 caching, and second-level
embeddings pruned after the build and regenerated on demand by the same
encoder.  Then it answers batches of requests: query embedding → centroid
top-k (``ivf_topk``) → cluster resolution from cache, storage or
regeneration → packed-slab scoring (``slab_topk``) → greedy generation
with sheared-llama-2.7b.  Both models run at their published widths with
weights initialised from ``--seed``; the generator holds bf16 parameters,
since its float32 parameters and prefill do not fit one 16 GB chip.

  python -m repro.launch.serve                              # full width
  python -m repro.launch.serve --reduced --records 2000     # CPU rehearsal

``--reduced`` swaps in the 2-layer, 256-wide configs of both models, for a
rehearsal of the same path on a CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro import configs
from repro.core import EdgeCostModel, EdgeRAGIndex
from repro.data.embedder import ModelEmbedder
from repro.data.synthetic import BEIR_SPECS, SyntheticDataset, scaled_beir
from repro.launch.compile_cache import configure_compile_cache
from repro.serving.engine import GeneratorModel, RAGEngine, RAGResponse

CHARS_PER_WORD = 6      # the synthetic vocabulary's mean word plus a space


@dataclasses.dataclass
class ServeRun:
    """What :func:`build` set up and :func:`answer` served."""
    ds: SyntheticDataset
    embedder: ModelEmbedder
    index: EdgeRAGIndex
    engine: RAGEngine
    corpus_emb: np.ndarray          # build-time embeddings (pruned from index)
    setup_s: Dict[str, float]       # wall seconds of each set-up phase
    query_embs: List[np.ndarray] = dataclasses.field(default_factory=list)
    responses: List[RAGResponse] = dataclasses.field(default_factory=list)
    batch_wall_s: List[float] = dataclasses.field(default_factory=list)


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dataset", default="fiqa", choices=list(BEIR_SPECS))
    ap.add_argument("--records", type=int, default=None,
                    help="corpus passages (default: the dataset's Table 2 "
                         "row)")
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8,
                    help="queries per served batch")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--nprobe", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--arch", default="sheared-llama-2.7b",
                    help="generator architecture (any config id)")
    ap.add_argument("--no-generator", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer, 256-wide models: CPU rehearsal")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build(args: argparse.Namespace) -> ServeRun:
    """Generate the corpus, embed it with the encoder, build the index and
    load the generator; every phase runs on JAX's default device."""
    import jax.numpy as jnp
    configure_compile_cache()
    setup: Dict[str, float] = {}
    t0 = time.perf_counter()
    spec = BEIR_SPECS[args.dataset]
    ds = scaled_beir(args.dataset, n_records=args.records or spec.n_records,
                     n_queries=args.queries, seed=args.seed,
                     mean_chunk_chars=round(spec.doc_words * CHARS_PER_WORD))
    enc_cfg = configs.get_config("gte-base-en-v1.5")
    gen_cfg = configs.get_config(args.arch)
    if args.reduced:
        enc_cfg, gen_cfg = enc_cfg.reduced(), gen_cfg.reduced()
    embedder = ModelEmbedder(enc_cfg, seed=args.seed)
    setup["corpus_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    corpus_emb = embedder(ds.texts)
    setup["embed_corpus_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    cost = EdgeCostModel()
    index = EdgeRAGIndex(embedder.dim, embedder, ds.get_chunks, cost,
                         slo_s=spec.slo_s)
    index.build(ds.chunk_ids, ds.texts, nlist=max(16, ds.n // 32),
                embeddings=corpus_emb, seed=args.seed)
    setup["build_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    gen = None
    if not args.no_generator:
        gen = GeneratorModel(gen_cfg, seed=args.seed,
                             dtype=None if args.reduced else jnp.bfloat16)
    engine = RAGEngine(index, gen, cost_model=cost, k=args.k,
                       nprobe=args.nprobe,
                       max_new_tokens=args.max_new_tokens)
    setup["generator_s"] = time.perf_counter() - t0
    return ServeRun(ds=ds, embedder=embedder, index=index, engine=engine,
                    corpus_emb=corpus_emb, setup_s=setup)


def answer(run: ServeRun, args: argparse.Namespace) -> ServeRun:
    """Answer ``args.queries`` requests in batches of ``args.batch``: each
    batch embeds its query texts, then one ``answer_batch`` retrieves and
    generates."""
    ds, engine = run.ds, run.engine
    for s in range(0, args.queries, args.batch):
        texts = ds.query_texts[s:s + args.batch]
        t0 = time.perf_counter()
        embs = run.embedder(texts)
        run.responses += engine.answer_batch(texts, embs, ds.get_chunks)
        run.batch_wall_s.append(time.perf_counter() - t0)
        run.query_embs.append(embs)
    return run


def serve(args: argparse.Namespace) -> ServeRun:
    return answer(build(args), args)


def counts(run: ServeRun) -> Dict[str, int]:
    """Cluster resolutions over every served request, by tier."""
    lats = [r.retrieval for r in run.responses]
    return {"n_generated": sum(l.n_generated for l in lats),
            "n_storage_loads": sum(l.n_storage_loads for l in lats),
            "n_cache_hits": sum(l.n_cache_hits for l in lats),
            "n_shared_hits": sum(l.n_shared_hits for l in lats),
            "stored_clusters": int(run.index.stats()["stored_clusters"])}


def main(argv: Optional[Sequence[str]] = None):
    args = parse_args(argv)
    run = serve(args)
    print(f"indexed {run.ds.n} passages into {run.index.nlist} clusters; "
          f"set-up wall s: {run.setup_s}")
    for bi, w in enumerate(run.batch_wall_s):
        print(f"batch {bi}: {w:.4f} s wall (queries embedded, retrieved "
              f"and answered)")
    for qi, r in enumerate(run.responses[:3]):
        print(f"q{qi}: retrieved {r.chunk_ids[:5]}... "
              f"tokens={r.output_tokens[:8]}")
    print(f"tiers: {counts(run)}")
    print(f"cache hit rate {run.index.cache.hit_rate:.2f}; resident index "
          f"{run.index.memory_bytes() / 2**20:.1f} MiB; storage "
          f"{run.index.storage_bytes() / 2**20:.1f} MiB")


if __name__ == "__main__":
    main()
