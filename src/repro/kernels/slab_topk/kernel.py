"""Pallas TPU kernel: ragged multi-query top-k over a packed cluster slab.

The batch's unique probed clusters live packed exactly once in one
contiguous (N, D) slab; each query's probe set is a *subset* of the slab's
rows.  The grid is (Q // BLOCK_Q, N // BLOCK_N) with N minor (sequential),
like ``ivf_topk`` — but the masking input ``virt`` (Q, N) int32 makes the
scan ragged: a row only competes for query q when ``virt[q, r] <
NOT_PROBED``, and ``virt`` doubles as the tie-break key (the row's position
in q's virtual per-query concatenation), so the selected rows are exactly
``jax.lax.top_k`` over the virtual concat the pre-slab per-query loop
materialized Q times.

Fused dequantization: the slab block is loaded HBM->VMEM in its compact
storage dtype.  int8 dots in f32 and applies the per-row scale to the
(BLOCK_Q, BLOCK_N) score block — one multiply per score instead of per
element, and no (N, D) fp32 copy ever materializes.  Mosaic cannot load an
fp16 block on TPU, so compiling an fp16 slab raises; it is never widened
to fp32 or sent to the reference behind the caller's back.

PQ (fourth representation): the slab block is the (m, BLOCK_N) uint8 code
matrix (codes transposed once per launch, so code j is a row) and the
per-query ADC tables (m, BLOCK_Q, 256) ride in as the second operand
(queries are not needed — the LUTs already are the query).  TPU VMEM has
no efficient dynamic gather, so the in-kernel gather+accumulate is
expressed as m one-hot matmuls: ``onehot(codes[j])`` is a (256, BLOCK_N)
selection matrix and ``luts[j] @ onehot`` lands on the MXU, accumulating
the exact same ``sum_j luts[q, j, code]`` as the reference gather.  No
decoded row and no codebook ever enter the kernel.

Top-k maintenance is k iterations of a row-vectorized lexicographic
(max-score, min-virt) select over the running (BLOCK_Q, k) best and the
(BLOCK_Q, BLOCK_N) score block, same shape of work as ``ivf_topk`` with
one extra reduction for the tie-break key.

All score matmuls run at ``Precision.HIGHEST`` (full f32 on the MXU), like
the reference, so the two agree to f32 rounding on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.slab_topk.ref import NEG_INF, NOT_PROBED

EXHAUSTED_KEY = float(2**31)    # tie-break key of an empty running lane
ROW_SENTINEL = 2**30
HIGHEST = jax.lax.Precision.HIGHEST


def _slab_merge_rows(scores, key, base, run_v, run_t, run_r, k: int):
    """Merge a block's (BQ, BN) scores into the running (BQ, k) best by
    (score desc, key asc).

    ``key`` is the block's f32 tie-break key: a member row's virt (exact,
    since virt < N < 2**24), ``NOT_PROBED`` for a non-member and, in the
    running block only, ``EXHAUSTED_KEY`` for an empty lane — so at equal
    (masked) score a real slab row always beats an empty lane.  Each of the
    k iterations takes the row max of the scores, the min key among the
    score-maximal candidates, then the min column among those, reading the
    winner's row out by a masked reduce and writing it to lane i with an
    iota compare (only f32 reductions: Mosaic lowers no gather, integer
    argmin or dynamic-lane store).  virt is unique per (query, member row),
    so the selection is a total order on members and the block-streaming
    merge equals a global sort.  A consumed candidate drops to -inf, below
    every masked (``NEG_INF``) score, so it is never selected twice.
    """
    bq, bn = scores.shape
    col_k = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1).astype(
        jnp.float32)
    col_n = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1).astype(
        jnp.float32)
    run_r_f = run_r.astype(jnp.float32)      # rows < 2**24 or ROW_SENTINEL

    def body(i, carry):
        rv, bv, out_v, out_t, out_r = carry
        m = jnp.maximum(jnp.max(rv, axis=1, keepdims=True),
                        jnp.max(bv, axis=1, keepdims=True))      # (BQ, 1)
        r_top, b_top = rv == m, bv == m
        kr = jnp.min(jnp.where(r_top, run_t, EXHAUSTED_KEY), axis=1,
                     keepdims=True)
        kb = jnp.min(jnp.where(b_top, key, EXHAUSTED_KEY), axis=1,
                     keepdims=True)
        from_run = kr <= kb
        best_t = jnp.minimum(kr, kb)
        jr = jnp.min(jnp.where(r_top & (run_t == best_t), col_k, float(k)),
                     axis=1, keepdims=True)
        jb = jnp.min(jnp.where(b_top & (key == best_t), col_n, float(bn)),
                     axis=1, keepdims=True)
        run_row = jnp.max(jnp.where(col_k == jr, run_r_f, -1.0), axis=1,
                          keepdims=True)
        best_r = jnp.where(from_run, run_row.astype(jnp.int32),
                           base + jb.astype(jnp.int32))
        lane = col_k == i
        out_v = jnp.where(lane, m, out_v)
        out_t = jnp.where(lane, best_t, out_t)
        out_r = jnp.where(lane, best_r, out_r)
        rv = jnp.where(from_run & (col_k == jr), -jnp.inf, rv)
        bv = jnp.where(~from_run & (col_n == jb), -jnp.inf, bv)
        return rv, bv, out_v, out_t, out_r

    init = (run_v, scores,
            jnp.full((bq, k), NEG_INF, jnp.float32),
            jnp.full((bq, k), EXHAUSTED_KEY, jnp.float32),
            jnp.full((bq, k), ROW_SENTINEL, jnp.int32))
    _, _, out_v, out_t, out_r = jax.lax.fori_loop(0, k, body, init)
    return out_v, out_t, out_r


def _kernel(emb_ref, q_ref, virt_ref, *rest,
            k: int, block_n: int, block_q: int, mode: str):
    if mode == "scaled":
        scale_ref, out_v_ref, out_r_ref, run_v, run_t, run_r = rest
    else:
        out_v_ref, out_r_ref, run_v, run_t, run_r = rest
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        run_v[...] = jnp.full((block_q, k), NEG_INF, jnp.float32)
        run_t[...] = jnp.full((block_q, k), EXHAUSTED_KEY, jnp.float32)
        run_r[...] = jnp.full((block_q, k), ROW_SENTINEL, jnp.int32)

    if mode == "pq":
        # ADC via one-hot matmul (module docstring): q_ref holds the
        # per-query LUTs (m, BQ, 256), emb_ref the codes (m, BN)
        codes = emb_ref[...].astype(jnp.int32)               # (m, BN)
        code_id = jax.lax.broadcasted_iota(jnp.int32, (256, block_n), 0)
        scores = jnp.zeros((block_q, block_n), jnp.float32)
        for j in range(codes.shape[0]):                      # m is static
            onehot = (codes[j:j + 1, :] == code_id).astype(jnp.float32)
            scores = scores + jax.lax.dot_general(           # (BQ, BN) MXU
                q_ref[j].astype(jnp.float32), onehot,
                (((1,), (0,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)
    else:
        emb = emb_ref[...].astype(jnp.float32)               # (BN, D) widen
        q = q_ref[...].astype(jnp.float32)                   # (BQ, D)
        scores = jax.lax.dot_general(                        # (BQ, BN) MXU
            q, emb, (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        if mode == "scaled":
            # fused dequant: per-row scale on the score block, not the slab
            scores = scores * scale_ref[...].astype(jnp.float32).T  # (1, BN)
    virt = virt_ref[...]                                     # (BQ, BN)
    member = virt < NOT_PROBED
    scores = jnp.where(member, scores, NEG_INF)
    key = jnp.where(member, virt, NOT_PROBED).astype(jnp.float32)
    v, t, r = _slab_merge_rows(scores, key, nb * block_n,
                               run_v[...], run_t[...], run_r[...], k)
    run_v[...] = v
    run_t[...] = t
    run_r[...] = r

    @pl.when(nb == pl.num_programs(1) - 1)
    def _done():
        out_v_ref[...] = run_v[...]
        out_r_ref[...] = run_r[...]


@functools.partial(jax.jit, static_argnames=("k", "block_n", "block_q",
                                             "interpret"))
def slab_topk_pallas(emb, queries, virt, k: int, scales=None, luts=None, *,
                     block_n: int = 512, block_q: int = 8,
                     interpret: bool = True):
    """emb (N, D) f32/f16/int8 — or (N, m) uint8 PQ codes when ``luts``
    (Q, m, 256) is given; queries (Q, D) f32, virt (Q, N) int32, scales
    (N, 1) f16/f32 or None -> (vals (Q, k) f32, rows (Q, k) int32).

    Pads N and Q to block multiples internally; padded slab rows get
    ``virt = NOT_PROBED`` so they never score, padded query rows are
    sliced off.  Requires k <= N (the ops layer clamps).  fp16 slabs run
    in interpret mode only (see the module docstring).
    """
    if emb.dtype == jnp.float16 and not interpret:
        raise NotImplementedError(
            "slab_topk: Mosaic cannot load fp16 slab blocks on TPU; store "
            "the tier as fp32 or int8")
    n, d = emb.shape
    if n >= 2**24:
        raise ValueError(f"{n} slab rows: virt keys and rows must stay "
                         "below 2**24 to be exact in the f32 merge")
    nq = virt.shape[0]
    block_q = max(1, min(block_q, nq))
    n_pad = (-n) % block_n
    if n_pad:
        emb = jnp.pad(emb, ((0, n_pad), (0, 0)))
        virt = jnp.pad(virt, ((0, 0), (0, n_pad)),
                       constant_values=NOT_PROBED)
        if scales is not None:
            scales = jnp.pad(scales, ((0, n_pad), (0, 0)))
    q_pad = (-nq) % block_q
    if q_pad:
        virt = jnp.pad(virt, ((0, q_pad), (0, 0)),
                       constant_values=NOT_PROBED)
        if luts is not None:
            luts = jnp.pad(luts, ((0, q_pad), (0, 0), (0, 0)))
        else:
            queries = jnp.pad(queries, ((0, q_pad), (0, 0)))
    n_blocks = emb.shape[0] // block_n
    q_blocks = virt.shape[0] // block_q

    mode = "pq" if luts is not None else (
        "scaled" if scales is not None else "fp32")
    kernel = functools.partial(_kernel, k=k, block_n=block_n,
                               block_q=block_q, mode=mode)
    if mode == "pq":
        # queries never enter the kernel: the LUTs replace them.  Code j
        # and table j become leading-axis rows (module docstring).
        emb = emb.T                                      # (m, N)
        q_operand = jnp.transpose(luts, (1, 0, 2))       # (m, Q, 256)
        emb_spec = pl.BlockSpec((d, block_n), lambda qi, ni: (0, ni))
        q_spec = pl.BlockSpec((d, block_q, 256), lambda qi, ni: (0, qi, 0))
    else:
        q_operand = queries
        emb_spec = pl.BlockSpec((block_n, d), lambda qi, ni: (ni, 0))
        q_spec = pl.BlockSpec((block_q, d), lambda qi, ni: (qi, 0))
    in_specs = [
        emb_spec,
        q_spec,
        pl.BlockSpec((block_q, block_n), lambda qi, ni: (qi, ni)),
    ]
    operands = [emb, q_operand, virt]
    if mode == "scaled":
        in_specs.append(pl.BlockSpec((block_n, 1), lambda qi, ni: (ni, 0)))
        operands.append(scales)
    out_v, out_r = pl.pallas_call(
        kernel,
        grid=(q_blocks, n_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((virt.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((virt.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32),
        ],
        interpret=interpret,
        name="slab_topk",
    )(*operands)
    if q_pad:
        out_v, out_r = out_v[:nq], out_r[:nq]
    return out_v, out_r
