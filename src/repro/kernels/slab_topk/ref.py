"""Pure-jnp oracle for the packed-slab ragged multi-query top-k.

Contract: the batch's unique probed clusters are packed ONCE into one
contiguous slab ``emb`` (N, D) — fp32, fp16, or int8 (+ per-row scales).
``virt`` (Q, N) int32 encodes both membership and ordering: ``virt[q, r]``
is row ``r``'s position in query ``q``'s *virtual* per-query concatenation
(its probed clusters laid out in probe order), or :data:`NOT_PROBED` when
query ``q`` did not probe the cluster owning row ``r``.

Selection per query is the best k rows by (score DESC, virt ASC).  The
virtual-index tie-break makes the result *identical* — ids included — to
``jax.lax.top_k`` over the per-query concatenated matrix the pre-slab
scoring loop built, so the fp32 slab path stays bit-compatible with the
sequential per-query reference while scoring every query in one launch.

Fused dequantization: fp16 slabs are widened in the score matmul (exact —
fp16 -> f32 is lossless, bit-identical to dequantize-then-score); int8
slabs apply the per-row fp16 scale to the (Q, N) score block AFTER the
integer-valued dot product instead of scaling all N*D elements first
(one multiply per score, not per element — equal to dequantize-then-score
up to a single f32 rounding per score).

PQ (fourth representation): ``emb`` is the (N, m) uint8 code matrix and
``luts`` the per-query ADC tables (Q, m, 256) built ONCE per batch by
``core.pq.pq_luts``.  A row's asymmetric inner-product score is
``sum_j luts[q, j, emb[r, j]]`` — m gathers + adds, never touching the
codebook or a decoded fp32 row.  Equal to decode-then-score up to f32
summation order (each term IS the exact subspace inner product).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NOT_PROBED = 2**30          # virt sentinel: row not in this query's probe set
NEG_INF = -1e30
_EXHAUSTED = NOT_PROBED + 1  # virt key of an already-selected row


def lex_topk(masked: jax.Array, virt: jax.Array, k: int):
    """Best k columns per row by (masked DESC, virt ASC), exactly.

    XLA CPU only fast-paths ``lax.top_k`` on f32 — integer top-k and every
    variadic ``lax.sort`` fall back to a ~50x slower generic path — so the
    lexicographic selection runs in two f32-friendly phases:

      1. ``lax.top_k(masked, k)``: the selected VALUE multiset is
         independent of how ties break, so the returned (sorted, ties
         adjacent) values are already exact.
      2. k iterations of a row-vectorized argmin: lane i takes the
         minimum-virt not-yet-taken column whose value compare-equals
         ``vals[:, i]`` — consecutive equal-value lanes therefore walk the
         tie group in ascending virt order, reproducing ``lax.top_k``'s
         stable equal-compare behavior on the virtual concat (including
         the -0.0 == +0.0 corner; returned vals are re-gathered from
         ``masked`` so even their sign bits match).
    """
    vals, _ = jax.lax.top_k(masked, k)                       # (Q, k)
    col = jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
    tie0 = jnp.where(virt < NOT_PROBED, virt, NOT_PROBED)

    def body(i, carry):
        tie, rows = carry
        target = jax.lax.dynamic_slice_in_dim(vals, i, 1, axis=1)  # (Q, 1)
        j = jnp.argmin(jnp.where(masked == target, tie, _EXHAUSTED),
                       axis=1)                               # (Q,)
        rows = jax.lax.dynamic_update_slice(
            rows, j[:, None].astype(jnp.int32), (0, i))
        tie = jnp.where(col == j[:, None], _EXHAUSTED, tie)  # consume
        return tie, rows

    _, rows = jax.lax.fori_loop(
        0, k, body, (tie0, jnp.zeros((masked.shape[0], k), jnp.int32)))
    return jnp.take_along_axis(masked, rows, axis=1), rows


def pq_adc_scores(codes: jax.Array, luts: jax.Array) -> jax.Array:
    """Asymmetric-distance scores from PQ codes: codes (N, m) integer,
    luts (Q, m, 256) f32 -> (Q, N) f32 with
    ``out[q, r] = sum_j luts[q, j, codes[r, j]]``."""
    codes = codes.astype(jnp.int32)
    m = codes.shape[1]
    nq, n = luts.shape[0], codes.shape[0]

    def body(j, acc):
        lut_j = jax.lax.dynamic_index_in_dim(luts, j, 1, keepdims=False)
        c_j = jax.lax.dynamic_index_in_dim(codes, j, 1, keepdims=False)
        return acc + jnp.take(lut_j, c_j, axis=1)        # gather (Q, N)

    return jax.lax.fori_loop(0, m, body, jnp.zeros((nq, n), jnp.float32))


def slab_topk_ref(emb: jax.Array, queries: jax.Array, virt: jax.Array,
                  k: int, scales: Optional[jax.Array] = None,
                  luts: Optional[jax.Array] = None):
    """emb (N, D) f32/f16/int8 — or (N, m) uint8 PQ codes when ``luts``
    (Q, m, 256) is given; queries (Q, D) f32; virt (Q, N) int32; scales
    (N, 1) f32 per-row (int8 slabs) or None.

    Returns (vals (Q, k) f32, rows (Q, k) int32): the best k slab rows per
    query by (score desc, virt asc).  Lanes beyond a query's candidate
    count carry ``NEG_INF`` scores and arbitrary member-free rows —
    callers mask by the per-query valid count.  Requires k <= N (dispatch
    clamps).
    """
    if luts is not None:
        scores = pq_adc_scores(emb, luts.astype(jnp.float32))
    else:
        scores = jnp.matmul(queries.astype(jnp.float32),
                            emb.astype(jnp.float32).T,
                            precision=jax.lax.Precision.HIGHEST)
        if scales is not None:
            scores = scores * scales.astype(jnp.float32)[:, 0][None, :]
    masked = jnp.where(virt < NOT_PROBED, scores, NEG_INF)
    return lex_topk(masked, virt, k)
