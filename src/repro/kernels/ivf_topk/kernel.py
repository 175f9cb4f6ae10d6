"""Pallas TPU kernel: fused inner-product distance + running top-k scan.

The compute hot-spot EdgeRAG inherits from FAISS is the second-level search:
score every candidate embedding in the probed clusters against the query and
keep the best k.  FAISS does a CPU linear scan; the TPU-native formulation
streams candidate rows HBM→VMEM and fuses the MXU distance matmul with an
on-chip running top-k, so no (N,) score vector ever hits HBM.

Multi-query tiling: queries are processed in blocks of ``block_q`` rows with
grid (Q // BLOCK_Q, N // BLOCK_N) — the N axis is the minor (sequential)
grid dim, so the (BLOCK_Q, k) running-best VMEM scratch persists across
candidate blocks of one query block.  Each candidate block is therefore
streamed from HBM once per *query block* instead of once per query: a batch
of B queries costs ceil(B / BLOCK_Q) passes over the candidates, not B.

Top-k maintenance is k iterations of a row-vectorized select over the
running (BLOCK_Q, k) best and the (BLOCK_Q, BLOCK_N) score block — all
BLOCK_Q rows advance per iteration (pure VPU work; k is small, ≤ 128).  It
uses only what Mosaic lowers: f32 max/min lane reductions, iota compares
and selects.  No gather, no integer argmin and no dynamic-lane store: the
winning column is the min column index (exact in f32) among the maximal
scores, its id is read out by a masked reduce, and lane i of the new
running block is written by ``where(iota_k == i, ...)``.  The single-query
path is the degenerate case BLOCK_Q = 1.

BlockSpec tiling: emb block (BLOCK_N, D) f32 in VMEM (default 512×768×4 ≈
1.5 MiB), query block (BLOCK_Q, D), outputs (BLOCK_Q, k).  D stays whole:
dim 768 = 6×128 lanes, MXU-aligned.  The true candidate count rides in SMEM
so padded rows can be masked; padded query rows are sliced off outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
IDX_SENTINEL = 2**30            # id of an empty running lane (exact in f32)


def _topk_merge_rows(scores, base, run_vals, run_idx, k: int):
    """Merge a block's scores (BQ, BN) into the running (BQ, k) best.

    ``base`` is the block's first global row.  Ties break toward the lower
    row id, matching ``jax.lax.top_k``: running entries come from earlier
    blocks, so at equal score they win over the block, and inside either
    part the lower column is the lower id.  Column indices and ids (rows
    < 2**24, or ``IDX_SENTINEL``) are exact in f32, so every reduction
    runs in f32.  A consumed candidate drops to -inf, below every masked
    (``NEG_INF``) score, so it is never selected twice.
    """
    bq, bn = scores.shape
    col_k = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1).astype(
        jnp.float32)
    col_n = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1).astype(
        jnp.float32)
    run_idx_f = run_idx.astype(jnp.float32)

    def body(i, carry):
        run_v, blk_v, out_v, out_i = carry
        m = jnp.maximum(jnp.max(run_v, axis=1, keepdims=True),
                        jnp.max(blk_v, axis=1, keepdims=True))   # (BQ, 1)
        jr = jnp.min(jnp.where(run_v == m, col_k, float(k)), axis=1,
                     keepdims=True)
        jb = jnp.min(jnp.where(blk_v == m, col_n, float(bn)), axis=1,
                     keepdims=True)
        from_run = jr < k
        run_id = jnp.max(jnp.where(col_k == jr, run_idx_f, -1.0), axis=1,
                         keepdims=True)
        best_i = jnp.where(from_run, run_id.astype(jnp.int32),
                           base + jb.astype(jnp.int32))
        lane = col_k == i
        out_v = jnp.where(lane, m, out_v)
        out_i = jnp.where(lane, best_i, out_i)
        run_v = jnp.where(from_run & (col_k == jr), -jnp.inf, run_v)
        blk_v = jnp.where(~from_run & (col_n == jb), -jnp.inf, blk_v)
        return run_v, blk_v, out_v, out_i

    init = (run_vals, scores,
            jnp.full((bq, k), NEG_INF, jnp.float32),
            jnp.full((bq, k), IDX_SENTINEL, jnp.int32))
    _, _, out_v, out_i = jax.lax.fori_loop(0, k, body, init)
    return out_v, out_i


def _kernel(valid_ref, emb_ref, q_ref, out_v_ref, out_i_ref,
            run_v, run_i, *, k: int, block_n: int, block_q: int):
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        run_v[...] = jnp.full((block_q, k), NEG_INF, jnp.float32)
        run_i[...] = jnp.full((block_q, k), IDX_SENTINEL, jnp.int32)

    emb = emb_ref[...].astype(jnp.float32)                   # (BN, D)
    q = q_ref[...].astype(jnp.float32)                       # (BQ, D)
    scores = jax.lax.dot_general(                            # (BQ, BN) via MXU
        q, emb, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    base = nb * block_n
    row = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(row < valid_ref[0], scores, NEG_INF)
    v, i = _topk_merge_rows(scores, base, run_v[...], run_i[...], k)
    run_v[...] = v
    run_i[...] = i

    @pl.when(nb == pl.num_programs(1) - 1)
    def _done():
        out_v_ref[...] = run_v[...]
        out_i_ref[...] = run_i[...]


@functools.partial(jax.jit,
                   static_argnames=("k", "block_n", "block_q", "interpret"))
def topk_ip_pallas(embs, queries, k: int, *, block_n: int = 512,
                   block_q: int = 8, interpret: bool = True):
    """embs (N, D) f32, queries (Q, D) f32 -> (scores (Q,k), idx (Q,k)).

    Queries are tiled in blocks of ``block_q`` (clamped to Q); each
    candidate block is read once per query block.  Q and N are padded to
    block multiples internally; padded outputs are sliced off.
    """
    n, d = embs.shape
    if n >= 2**24:
        raise ValueError(f"{n} candidate rows: row ids must stay below "
                         "2**24 to be exact in the f32 merge")
    q = queries.shape[0]
    block_q = max(1, min(block_q, q))
    n_pad = (-n) % block_n
    if n_pad:
        embs = jnp.pad(embs, ((0, n_pad), (0, 0)))
    q_pad = (-q) % block_q
    if q_pad:
        queries = jnp.pad(queries, ((0, q_pad), (0, 0)))
    n_blocks = embs.shape[0] // block_n
    q_blocks = queries.shape[0] // block_q
    valid = jnp.array([n], jnp.int32)

    kernel = functools.partial(_kernel, k=k, block_n=block_n,
                               block_q=block_q)
    out_v, out_i = pl.pallas_call(
        kernel,
        grid=(q_blocks, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_n, d), lambda qi, ni: (ni, 0)),
            pl.BlockSpec((block_q, d), lambda qi, ni: (qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((queries.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((queries.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
        name="ivf_topk",
    )(valid, embs, queries)
    if q_pad:
        out_v, out_i = out_v[:q], out_i[:q]
    return out_v, out_i
