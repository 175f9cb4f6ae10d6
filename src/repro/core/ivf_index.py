"""Two-level Inverted File (IVF) index — the paper's latency baseline
(Table 4 row 2) and the substrate EdgeRAG modifies.

Level 1: cluster centroids, always resident.  Level 2: per-cluster chunk
embeddings, resident in memory for the baseline.  Retrieval probes the
``nprobe`` nearest centroids and scans their clusters.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.costs import EdgeCostModel, LatencyBreakdown
from repro.core.kmeans import kmeans
from repro.core.tracing import span
from repro.kernels.ivf_topk.ops import topk_ip


@dataclasses.dataclass
class Cluster:
    ids: np.ndarray                       # (n,) chunk ids
    embeddings: Optional[np.ndarray]      # (n, d) or None when pruned

    @property
    def size(self) -> int:
        return len(self.ids)


class IVFIndex:
    def __init__(self, dim: int, cost_model: Optional[EdgeCostModel] = None):
        self.dim = dim
        self.cost = cost_model or EdgeCostModel()
        self.centroids: Optional[np.ndarray] = None          # (nlist, d)
        self.clusters: List[Cluster] = []

    # ------------------------------------------------------------------
    def build(self, embeddings: np.ndarray, ids: np.ndarray,
              nlist: int, kmeans_iters: int = 20, seed: int = 0):
        embeddings = np.ascontiguousarray(embeddings, np.float32)
        ids = np.asarray(ids, np.int64)
        self.centroids, assign = kmeans(embeddings, nlist,
                                        iters=kmeans_iters, seed=seed)
        self.clusters = []
        for c in range(self.centroids.shape[0]):
            sel = np.where(assign == c)[0]
            self.clusters.append(
                Cluster(ids=ids[sel],
                        embeddings=np.ascontiguousarray(embeddings[sel])))
        return assign

    @property
    def nlist(self) -> int:
        return 0 if self.centroids is None else len(self.centroids)

    @property
    def ntotal(self) -> int:
        return sum(c.size for c in self.clusters)

    def memory_bytes(self) -> int:
        n = self.centroids.nbytes if self.centroids is not None else 0
        for c in self.clusters:
            if c.embeddings is not None:
                n += c.embeddings.nbytes
        return n

    # ------------------------------------------------------------------
    def probe(self, query: np.ndarray, nprobe: int) -> np.ndarray:
        """(Q, d) -> (Q, nprobe) centroid indices."""
        query = np.atleast_2d(np.asarray(query, np.float32))
        _, idx = topk_ip(self.centroids, query, min(nprobe, self.nlist))
        return np.asarray(idx)

    def search(self, query: np.ndarray, k: int, nprobe: int
               ) -> Tuple[np.ndarray, np.ndarray, LatencyBreakdown]:
        """Single query (d,) or (1, d)."""
        query = np.atleast_2d(np.asarray(query, np.float32))
        assert query.shape[0] == 1, "IVF search is per-query"
        lat = LatencyBreakdown()
        with span("ivf.search") as t:
            probed = self.probe(query, nprobe)[0]
            lat.n_clusters_probed = len(probed)
            cand_embs, cand_ids, scanned = [], [], 0
            for c in probed:
                cl = self.clusters[int(c)]
                if cl.size == 0 or cl.embeddings is None:
                    continue
                cand_embs.append(cl.embeddings)
                cand_ids.append(cl.ids)
                scanned += cl.size
            if not cand_embs:
                empty = np.full((1, k), -1, np.int64)
                return empty, np.full((1, k), -np.inf, np.float32), lat
            embs = np.concatenate(cand_embs)
            idmap = np.concatenate(cand_ids)
            vals, idx = topk_ip(embs, query, k)
            vals, idx = np.asarray(vals), np.asarray(idx)
        lat.wall_s = t.elapsed
        lat.centroid_search_s = (
            self.cost.mem_load_latency(self.centroids.nbytes)
            + self.cost.search_latency(self.nlist, self.dim))
        # level-2: touched cluster embeddings load from "memory"; the
        # RESIDENT SET is the whole in-memory index (this is what thrashes)
        lat.l2_mem_load_s = self.cost.mem_load_latency(
            embs.nbytes, resident_bytes=self.memory_bytes())
        lat.l2_search_s = self.cost.search_latency(scanned, self.dim)
        ids = np.where(idx >= 0, idmap[np.clip(idx, 0, len(idmap) - 1)], -1)
        return ids, vals, lat
