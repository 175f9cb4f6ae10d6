"""Beyond-paper: pod-sharded second-level retrieval (DESIGN.md §2.1).

The paper's premise is one memory-starved device.  On a pod, EdgeRAG's
pruning is still what makes an index fit per-chip HBM next to the model —
and the second-level search itself parallelizes: candidate embeddings shard
round-robin over the "data" axis, every shard runs the fused top-k scan
over its local rows (the same ivf_topk hot loop the Pallas kernel
implements), and ONE all-gather of per-shard (k) candidates — k·shards
rows, not the corpus — merges globally.

Communication per query: shards × k × (4+4) bytes ≈ 16·10·8 = 1.3 kB.
A replicated scan would move nothing but duplicate ALL compute; gathering
raw candidates would move the whole probed set.  This is the standard
distributed-top-k trade.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.slab_topk.ops import ROW_PAD
from repro.kernels.slab_topk.ref import NOT_PROBED

NEG_INF = -1e30


def sharded_topk_ip(embs, queries, k: int, mesh, axis: str = "data"
                    ) -> Tuple[jax.Array, jax.Array]:
    """embs (N, D) row-sharded over ``axis``; queries (Q, D) replicated.

    Returns (scores (Q, k), global row idx (Q, k)) — identical to
    kernels.ivf_topk.ops.topk_ip on the gathered matrix.
    """
    n, d = embs.shape
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    pad = (-n) % n_shards
    if pad:
        embs = jnp.pad(embs, ((0, pad), (0, 0)))
    n_padded = embs.shape[0]

    def local_fn(emb_loc, q):
        shard = jax.lax.axis_index(axis)
        s_rows = emb_loc.shape[0]
        scores = jnp.matmul(q.astype(jnp.float32),
                            emb_loc.astype(jnp.float32).T,
                            precision=jax.lax.Precision.HIGHEST)
        base = shard * s_rows + jnp.arange(s_rows)
        scores = jnp.where((base < n)[None, :], scores, NEG_INF)
        kk = min(k, s_rows)
        vals, idx = jax.lax.top_k(scores, kk)              # (Q, kk) local
        gidx = base[idx]
        # gather the per-shard candidates everywhere, merge locally
        all_vals = jax.lax.all_gather(vals, axis, axis=1)  # (Q, S, kk)
        all_idx = jax.lax.all_gather(gidx, axis, axis=1)
        qn = all_vals.shape[0]
        flat_v = all_vals.reshape(qn, -1)
        flat_i = all_idx.reshape(qn, -1)
        mv, mi = jax.lax.top_k(flat_v, k)
        return mv, jnp.take_along_axis(flat_i, mi, axis=1).astype(jnp.int32)

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=(P(), P()),
        check_vma=False)
    with mesh:
        return fn(embs, queries)


def sharded_slab_topk(emb, queries, virt, k: int, mesh, axis: str = "data",
                      scales=None, luts=None) -> Tuple[jax.Array, jax.Array]:
    """Pod-sharded ragged multi-query top-k over ONE packed slab per batch.

    The pre-slab sharded route issued one ``sharded_topk_ip`` per query
    over that query's re-concatenated clusters — Q all-gathers and Q
    copies of every shared cluster.  Here the batch's packed slab ``emb``
    (N, D; fp32/fp16/int8 — or (N, m) uint8 PQ codes when ``luts`` is
    given) row-shards over ``axis`` together with its membership matrix
    ``virt`` (Q, N, sharded on N) and optional per-row ``scales`` (N, 1);
    the per-query PQ LUTs (Q, m, 256) replicate like the queries they
    stand in for.  Every shard scores its local rows for ALL queries with
    fused dequant (or LUT gather+accumulate), selects its local best-k by
    (score desc, virt asc), and one all-gather of k·shards candidates per
    query merges globally under the same total order.  Results are
    identical to ``kernels.slab_topk.slab_topk`` on the unsharded slab.
    """
    n, d = emb.shape
    nq = virt.shape[0]
    if n == 0 or k == 0:
        return (jnp.full((nq, k), -np.inf, jnp.float32),
                jnp.full((nq, k), ROW_PAD, jnp.int32))
    k_eff = min(k, n)      # same clamp-and-pad contract as ops.slab_topk
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    pad = (-n) % n_shards
    if pad:
        emb = jnp.pad(emb, ((0, pad), (0, 0)))
        virt = jnp.pad(virt, ((0, 0), (0, pad)),
                       constant_values=NOT_PROBED)
        if scales is not None:
            scales = jnp.pad(scales, ((0, pad), (0, 0)))
    kk = min(k_eff, emb.shape[0] // n_shards)

    def local_fn(emb_loc, q, virt_loc, *extras):
        from repro.kernels.slab_topk.ref import lex_topk, pq_adc_scores
        shard = jax.lax.axis_index(axis)
        s_rows = emb_loc.shape[0]
        if luts is not None:
            scores = pq_adc_scores(emb_loc, extras[0].astype(jnp.float32))
        else:
            scores = jnp.matmul(q.astype(jnp.float32),
                                emb_loc.astype(jnp.float32).T,
                                precision=jax.lax.Precision.HIGHEST)
            if extras:
                scores = scores * extras[0].astype(jnp.float32)[:, 0][None]
        masked = jnp.where(virt_loc < NOT_PROBED, scores, NEG_INF)
        # local best-kk by (score desc, virt asc)
        lvals, lidx = lex_topk(masked, virt_loc, kk)
        lvirt = jnp.take_along_axis(virt_loc, lidx, axis=1)
        lrows = shard * s_rows + lidx
        # gather the per-shard candidates everywhere, merge locally under
        # the SAME total order
        av = jax.lax.all_gather(lvals, axis, axis=1)        # (Q, S, kk)
        at = jax.lax.all_gather(lvirt, axis, axis=1)
        ar = jax.lax.all_gather(lrows, axis, axis=1)
        qn = av.shape[0]
        fv, ft, fr = (a.reshape(qn, -1) for a in (av, at, ar))
        mv, midx = lex_topk(fv, ft, k_eff)
        return mv, jnp.take_along_axis(fr, midx, axis=1).astype(jnp.int32)

    in_specs = [P(axis, None), P(None, None), P(None, axis)]
    operands = [emb, queries, virt]
    if luts is not None:
        in_specs.append(P(None, None, None))    # replicated, like queries
        operands.append(jnp.asarray(luts, jnp.float32))
    elif scales is not None:
        in_specs.append(P(axis, None))
        operands.append(scales)
    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=tuple(in_specs), out_specs=(P(), P()),
                   check_vma=False)
    with mesh:
        vals, rows = fn(*operands)
    if k_eff < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - k_eff)),
                       constant_values=-np.inf)
        rows = jnp.pad(rows, ((0, 0), (0, k - k_eff)),
                       constant_values=ROW_PAD)
    return vals, rows


class ShardedFlatSearch:
    """Pod-scale exhaustive search service over a pruned-or-not corpus slab.

    Used by the pod serving story (examples) and as the reference
    implementation the Pallas ivf_topk kernel would back on real hardware.
    """

    def __init__(self, embeddings: np.ndarray, mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.n = embeddings.shape[0]
        n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
        pad = (-self.n) % n_shards
        emb = np.pad(embeddings.astype(np.float32), ((0, pad), (0, 0)))
        sharding = NamedSharding(mesh, P(axis, None))
        self.embs = jax.device_put(jnp.asarray(emb), sharding)

    def search(self, queries: np.ndarray, k: int):
        q = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
        vals, idx = sharded_topk_ip(self.embs, q, k, self.mesh, self.axis)
        return np.asarray(vals), np.asarray(idx)
