"""Flat (exhaustive) index — the paper's quality baseline (Table 4 row 1).

Stores every chunk embedding in memory and linearly scans all of them per
query.  Retrieval is exact; the cost model charges the full resident set
(which is what thrashes on edge devices once the index outgrows DRAM —
Fig. 3).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core.costs import EdgeCostModel, LatencyBreakdown
from repro.core.tracing import span
from repro.kernels.ivf_topk.ops import topk_ip


class FlatIndex:
    def __init__(self, dim: int, cost_model: Optional[EdgeCostModel] = None):
        self.dim = dim
        self.cost = cost_model or EdgeCostModel()
        self._embs: Optional[np.ndarray] = None
        self._ids: Optional[np.ndarray] = None

    def add(self, embeddings: np.ndarray, ids: np.ndarray):
        embeddings = np.ascontiguousarray(embeddings, np.float32)
        ids = np.asarray(ids, np.int64)
        if self._embs is None:
            self._embs, self._ids = embeddings, ids
        else:
            self._embs = np.concatenate([self._embs, embeddings])
            self._ids = np.concatenate([self._ids, ids])

    @property
    def ntotal(self) -> int:
        return 0 if self._embs is None else len(self._embs)

    def memory_bytes(self) -> int:
        return 0 if self._embs is None else self._embs.nbytes

    def search(self, query: np.ndarray, k: int
               ) -> Tuple[np.ndarray, np.ndarray, LatencyBreakdown]:
        """query (Q, dim) -> (ids (Q,k), scores (Q,k), latency)."""
        query = np.atleast_2d(np.asarray(query, np.float32))
        lat = LatencyBreakdown()
        with span("flat.search") as t:
            vals, idx = topk_ip(self._embs, query, k)
            vals, idx = np.asarray(vals), np.asarray(idx)
        lat.wall_s = t.elapsed
        # sequential scan touches the whole index; thrashing if over-memory
        lat.l2_mem_load_s = self.cost.mem_load_latency(
            self._embs.nbytes, resident_bytes=self.memory_bytes())
        lat.l2_search_s = self.cost.search_latency(self.ntotal, self.dim)
        ids = np.where(idx >= 0, self._ids[np.clip(idx, 0, self.ntotal - 1)],
                       -1)
        return ids, vals, lat
