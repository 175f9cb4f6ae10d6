"""Pure-jnp oracle for the fused IVF second-level search.

Contract: given candidate embeddings (N, D) and queries (Q, D), return the
top-k inner-product scores and row indices per query.  Ties broken toward
the lower index (matches the kernel's strict-greater running merge).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_ip_ref(embs: jax.Array, queries: jax.Array, k: int):
    """embs: (N, D); queries: (Q, D) -> (scores (Q, k), idx (Q, k) int32)."""
    scores = jnp.matmul(queries.astype(jnp.float32),                 # (Q, N)
                        embs.astype(jnp.float32).T,
                        precision=jax.lax.Precision.HIGHEST)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)
