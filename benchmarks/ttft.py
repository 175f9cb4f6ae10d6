"""Fig. 13 + headline numbers: TTFT for all five Table 4 configurations
across the six BEIR datasets (paper-scale cost model), plus the REAL
laptop-scale pipeline TTFT (reduced models, synthetic corpus).

Paper validation targets: EdgeRAG vs IVF speedup ≈ 1.8x avg / 3.82x large
(abstract) — the paper's own conclusion restates these as 1.22x / 3.69x."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit
from repro.core import EdgeCostModel, EdgeRAGIndex
from repro.data.synthetic import BEIR_SPECS, scaled_beir
from repro.serving.engine import RAGEngine
from repro.serving.simulator import simulate_ttft

LARGE = ("nq", "hotpotqa", "fever")


def run(n_queries: int = 300, real_records: int = 1500, real_queries: int = 40):
    table = simulate_ttft(n_queries=n_queries)
    speedups = {}
    for ds, rows in table.items():
        for cfg, r in rows.items():
            emit(f"fig13/{ds}/{cfg}/ttft_s", r.mean_ttft_s * 1e6,
                 f"retr_s={r.mean_retrieval_s:.3f};p95_s={r.p95_s:.3f};"
                 f"resident_gib={r.resident_bytes/2**30:.3f};"
                 f"hit={r.cache_hit_rate:.2f};slo={r.slo_hit_rate:.2f}")
        speedups[ds] = rows["ivf"].mean_ttft_s / rows["edgerag"].mean_ttft_s
    avg = float(np.mean(list(speedups.values())))
    large = float(np.mean([speedups[d] for d in LARGE]))
    emit("headline/ttft_speedup_avg", 0.0,
         f"ours={avg:.2f}x;paper_abstract=1.8x;paper_conclusion=1.22x")
    emit("headline/ttft_speedup_large", 0.0,
         f"ours={large:.2f}x;paper_abstract=3.82x;paper_conclusion=3.69x")
    # cache memory overhead (paper: ~7% of system memory)
    er = table["fever"]["edgerag"]
    gen = table["fever"]["ivf_gen"]
    cost = EdgeCostModel()
    emit("headline/cache_memory_overhead", 0.0,
         f"frac_of_system={(er.resident_bytes - gen.resident_bytes)/cost.device_memory_bytes:.3f};paper=0.07")

    # REAL pipeline at laptop scale (relative ordering check)
    ds = scaled_beir("fever", n_records=real_records, n_queries=real_queries)
    cost = EdgeCostModel()
    er_idx = EdgeRAGIndex(ds.embeddings.shape[1], ds.embedder, ds.get_chunks,
                          cost, slo_s=BEIR_SPECS["fever"].slo_s)
    er_idx.build(ds.chunk_ids, ds.texts, nlist=max(32, ds.n // 32),
                 embeddings=ds.embeddings)
    engine = RAGEngine(er_idx, None, cost_model=cost, k=10, nprobe=8)
    ttfts, walls = [], []
    for qi in range(real_queries):
        resp = engine.answer(f"q{qi}", ds.query_embs[qi], ds.get_chunks)
        ttfts.append(resp.ttft_edge_s)
        walls.append(resp.ttft_wall_s)      # no generator: to prompt ready
    emit("real/fever_scaled/edgerag_ttft_edge_s",
         float(np.mean(ttfts)) * 1e6,
         f"to_prompt_wall_ms={np.mean(walls)*1e3:.1f};"
         f"hit={er_idx.cache.hit_rate:.2f}")


if __name__ == "__main__":
    run()
