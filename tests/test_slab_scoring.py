"""Packed-slab batch scoring engine: fp32 bitwise parity vs the sequential
per-query concat loop across the Table-4 configs (incl. empty probe lists
and merged-away clusters), fp16/int8 fused-dequant parity vs
dequant-then-score, PQ LUT-scoring differentials (ref + Pallas vs
decode-then-exact, mixed four-representation slabs vs per-segment merge),
slab layout structure, the raw-codec get_many contract, the ragged
multi-query Pallas kernel vs its jnp oracle, and the lazy-decay LFU cache
vs an eager reference."""
import numpy as np
import pytest

from repro.core import EdgeCostModel, EdgeRAGIndex
from repro.core.cache_policy import CostAwareLFUCache
from repro.core.costs import LatencyBreakdown
from repro.core.resolver import SlabPayload
from repro.data import generate_dataset
from repro.kernels.ivf_topk.ops import topk_ip
from repro.kernels.slab_topk.kernel import slab_topk_pallas
from repro.kernels.slab_topk.ops import NOT_PROBED, slab_topk
from repro.kernels.slab_topk.ref import slab_topk_ref
from repro.models.quantization import dequantize_rows, quantize_rows

pytestmark = pytest.mark.fast

# Table 4 ablation rows (see core/edgerag.py module docstring)
CONFIGS = {
    "embed_gen": dict(store_heavy=False, cache_bytes=0),
    "embed_gen_load": dict(store_heavy=True, cache_bytes=0),
    "edgerag": dict(store_heavy=True, cache_bytes=1 << 20),
}


@pytest.fixture(scope="module")
def ds():
    return generate_dataset(n_records=900, dim=32, n_topics=30,
                            n_queries=32, seed=11)


def _fresh(ds, **kw):
    kw.setdefault("slo_s", 0.3)
    er = EdgeRAGIndex(32, ds.embedder, ds.get_chunks, EdgeCostModel(), **kw)
    er.build(ds.chunk_ids, ds.texts, nlist=30, embeddings=ds.embeddings,
             seed=1)
    return er


def _per_query_loop(er, queries, k, nprobe, plan=None):
    """The pre-slab scoring path: resolve (decoded fp32), then per query
    concatenate its probed clusters in probe order and run topk_ip."""
    nq = queries.shape[0]
    if plan is None:
        plan = er.resolver.plan(er._probe(queries, nprobe))
    lats = [LatencyBreakdown() for _ in range(nq)]
    resolved = er.resolver.execute(plan, lats, [False] * nq)
    out_ids = np.full((nq, k), -1, np.int64)
    out_vals = np.full((nq, k), -np.inf, np.float32)
    for qi, probed in enumerate(plan.probed_per_q):
        if not probed:
            continue
        embs = np.concatenate([resolved[c] for c in probed])
        idmap = np.concatenate([er.clusters[c].ids for c in probed])
        if len(embs) == 0:
            continue
        vals, idx = topk_ip(embs, queries[qi:qi + 1], k)
        vals, idx = np.asarray(vals)[0], np.asarray(idx)[0]
        ok = idx >= 0
        out_vals[qi] = np.where(ok, vals, -np.inf)
        out_ids[qi] = np.where(ok, idmap[np.where(ok, idx, 0)], -1)
    return out_ids, out_vals


@pytest.mark.parametrize("cfg", list(CONFIGS))
def test_fp32_slab_bitwise_parity_vs_per_query_loop(ds, cfg):
    """The slab engine's ids == the sequential per-query concat + top-k
    loop exactly, scores to f32 rounding (Q=16 and Q=1 matmuls reduce in
    different orders), for every Table-4 ablation config."""
    nq = 16
    slab_er = _fresh(ds, **CONFIGS[cfg])
    loop_er = _fresh(ds, **CONFIGS[cfg])
    s_ids, s_vals, _ = slab_er.search_batch(ds.query_embs[:nq], 10, 5)
    l_ids, l_vals = _per_query_loop(loop_er, ds.query_embs[:nq], 10, 5)
    assert np.array_equal(s_ids, l_ids)
    np.testing.assert_allclose(s_vals, l_vals, rtol=1e-6, atol=1e-6)


def test_slab_parity_empty_probe_and_merged_away(ds):
    """A query whose probe list is empty and a cluster tombstoned between
    plan and execute (resolves to ZERO slab rows) both degrade exactly like
    the per-query loop: missing lanes pad with (-1, -inf), every id stays
    identical and every score equal to f32 rounding."""
    nq = 8
    slab_er = _fresh(ds, **CONFIGS["edgerag"])
    loop_er = _fresh(ds, **CONFIGS["edgerag"])
    plan_s = slab_er.plan_batch(ds.query_embs[:nq], 5)
    plan_l = loop_er.resolver.plan(loop_er._probe(ds.query_embs[:nq], 5))
    assert plan_s.probed_per_q == plan_l.probed_per_q
    # query 3's probe list empties; a cluster probed by several queries
    # tombstones (as a merge would) after both plans were taken
    victim = next(c for c in plan_s.probed_per_q[0]
                  if sum(c in p for p in plan_s.probed_per_q) > 1)
    for er in (slab_er, loop_er):
        plan = plan_s if er is slab_er else plan_l
        plan.probed_per_q[3] = []
        cl = er.clusters[victim]
        cl.active = False
        cl.ids = np.zeros((0,), np.int64)
        cl.char_count = 0
        cl.generation += 1
    s_ids, s_vals, _ = slab_er.search_batch(ds.query_embs[:nq], 10, 5,
                                            plan=plan_s)
    l_ids, l_vals = _per_query_loop(loop_er, ds.query_embs[:nq], 10, 5,
                                    plan=plan_l)
    assert np.array_equal(s_ids, l_ids)
    np.testing.assert_allclose(s_vals, l_vals, rtol=1e-6, atol=1e-6)
    assert (s_ids[3] == -1).all() and (s_vals[3] == -np.inf).all()


@pytest.mark.parametrize("codec", ["fp16", "int8"])
def test_quantized_fused_dequant_parity(ds, codec):
    """fp16/int8 slabs score with fused in-kernel dequantization; scores
    match dequantize-then-score within codec tolerance (fp16 widening is
    exact; int8 differs only by where the per-row scale multiply rounds)
    and the fused-dequant seconds are charged instead of decode seconds."""
    nq = 12
    fused = _fresh(ds, slo_s=1e-6, store_heavy=True, cache_bytes=0,
                   storage_codec=codec)
    deq = _fresh(ds, slo_s=1e-6, store_heavy=True, cache_bytes=0,
                 storage_codec=codec)
    f_ids, f_vals, lats = fused.search_batch(ds.query_embs[:nq], 10, 5)
    d_ids, d_vals = _per_query_loop(deq, ds.query_embs[:nq], 10, 5)
    np.testing.assert_allclose(f_vals, d_vals, atol=2e-5, rtol=1e-5)
    overlap = np.mean([len(set(f_ids[q]) & set(d_ids[q])) / 10
                       for q in range(nq)])
    assert overlap >= 0.9
    if codec == "fp16":       # lossless widen: same ids, f32-rounding scores
        assert np.array_equal(f_ids, d_ids)
        np.testing.assert_allclose(f_vals, d_vals, rtol=1e-6, atol=1e-6)
    assert sum(l.l2_fused_dequant_s for l in lats) > 0
    assert sum(l.l2_dequant_s for l in lats) == 0


def test_mixed_segment_slab_matches_per_query_loop(ds):
    """A batch whose slab mixes representations — int8 storage-tier
    clusters next to fp32 regen/cache clusters (mid-range SLO under a
    quantized codec) — exercises the cross-segment merge: results match
    the per-query dequant-then-score loop within codec tolerance."""
    nq = 12
    kw = dict(slo_s=0.1, store_heavy=True, cache_bytes=1 << 20,
              storage_codec="int8")
    slab_er = _fresh(ds, **kw)
    loop_er = _fresh(ds, **kw)
    # the config must actually produce a mixed slab, else this test rots
    plan = slab_er.plan_batch(ds.query_embs[:nq], 5)
    lats = [LatencyBreakdown() for _ in range(nq)]
    probe_slab = slab_er.resolver.execute_slab(plan, lats, [False] * nq)
    kinds = sorted(seg.kind for seg in probe_slab.segments)
    assert kinds == ["fp32", "int8"], kinds
    # fresh twins (the probe above advanced cache/threshold state)
    slab_er = _fresh(ds, **kw)
    loop_er = _fresh(ds, **kw)
    s_ids, s_vals, _ = slab_er.search_batch(ds.query_embs[:nq], 10, 5)
    l_ids, l_vals = _per_query_loop(loop_er, ds.query_embs[:nq], 10, 5)
    np.testing.assert_allclose(s_vals, l_vals, atol=2e-5, rtol=1e-5)
    overlap = np.mean([len(set(s_ids[q]) & set(l_ids[q])) / 10
                       for q in range(nq)])
    assert overlap >= 0.9
    # lane-aligned wherever scores are distinct enough to pin the order
    gap = np.abs(np.diff(l_vals, axis=1)) > 1e-4
    pinned = np.concatenate([gap, np.ones((nq, 1), bool)], axis=1) \
        & np.concatenate([np.ones((nq, 1), bool), gap], axis=1)
    assert (s_ids == l_ids)[pinned].mean() > 0.95


def test_slab_layout_packs_each_cluster_once(ds):
    """SlabLayout: every unique planned cluster appears exactly once, the
    extents tile the slab, the id slab parallels the embedding rows, and
    view() returns the packed rows."""
    er = _fresh(ds, **CONFIGS["edgerag"])
    nq = 12
    plan = er.plan_batch(ds.query_embs[:nq], 5)
    lats = [LatencyBreakdown() for _ in range(nq)]
    slab = er.resolver.execute_slab(plan, lats, [False] * nq)
    assert set(slab.extent) == set(plan.owner)
    assert len(slab.segments) == 1 and slab.segments[0].kind == "fp32"
    seg = slab.segments[0]
    covered = np.zeros(seg.rows, bool)
    for cid, (kind, off, length) in slab.extent.items():
        assert kind == "fp32"
        assert not covered[off:off + length].any()   # no overlap
        covered[off:off + length] = True
        assert length == er.clusters[cid].size
        assert np.array_equal(seg.ids[off:off + length],
                              er.clusters[cid].ids)
        view = slab.view(cid)
        assert view.base is seg.emb or view.size == 0   # a view, not a copy
        assert slab.nbytes(cid) == view.nbytes
    assert covered.all()                             # extents tile the slab
    # unique rows == sum of unique cluster sizes (each packed ONCE)
    assert seg.rows == sum(er.clusters[c].size for c in plan.owner)
    # pack cost charged once per unique cluster, to owners only
    assert sum(l.l2_slab_pack_s > 0 for l in lats) <= nq
    assert sum(l.l2_slab_pack_s for l in lats) == pytest.approx(
        sum(er.cost.slab_pack_latency(er.clusters[c].size * 32 * 4)
            for c in plan.owner))


@pytest.mark.parametrize("codec", ["fp32", "fp16", "int8", "pq"])
def test_get_many_raw_contract(ds, codec):
    """get_many_raw returns undecoded codec payloads in key order with
    None for missing keys; decode() reproduces get()."""
    er = _fresh(ds, slo_s=1e-6, store_heavy=True, cache_bytes=0,
                storage_codec=codec)
    keys = er.storage.keys()[:4]
    assert keys, "expected stored clusters under a tiny SLO"
    raw = er.storage.get_many_raw(keys + [10**9])
    assert raw[-1] is None
    for key, payload in zip(keys, raw):
        if codec == "int8":
            assert set(payload) == {"q", "scale"}
            assert payload["q"].dtype == np.int8
            assert payload["scale"].dtype == np.float16
        elif codec == "pq":
            assert set(payload) == {"codes", "cbv"}
            assert payload["codes"].dtype == np.uint8
        else:
            assert set(payload) == {"emb"}
            assert payload["emb"].dtype == (
                np.float16 if codec == "fp16" else np.float32)
        assert er.storage.payload_rows(payload) == er.clusters[key].size
        assert np.array_equal(er.storage.decode(payload),
                              er.storage.get(key))
        cb = er.storage.pq if codec == "pq" else None
        assert SlabPayload.from_raw(payload, cb).kind == codec


# ---------------------------------------------------------------------------
# ragged multi-query kernel vs oracle
# ---------------------------------------------------------------------------
def _random_slab_membership(rng, n, nq, n_clusters=6, max_probe=4):
    """Random cluster runs + per-query random probe subsets in random
    order; returns virt (Q, N) int32."""
    bounds = np.sort(rng.choice(np.arange(1, n), n_clusters - 1,
                                replace=False))
    bounds = [0, *bounds.tolist(), n]
    virt = np.full((nq, n), NOT_PROBED, np.int32)
    for q in range(nq):
        sel = rng.permutation(n_clusters)[:rng.integers(0, max_probe + 1)]
        base = 0
        for c in sel:
            o, e = bounds[c], bounds[c + 1]
            virt[q, o:e] = np.arange(base, base + (e - o))
            base += e - o
    return virt


@pytest.mark.parametrize("n,d,q,k,block_q,block_n,dtype", [
    (300, 32, 9, 7, 4, 64, "fp32"),     # ragged, every axis padded
    (256, 64, 8, 10, 8, 128, "fp32"),   # exact tiles
    (200, 32, 5, 33, 4, 64, "fp32"),    # k > some queries' member counts
    (300, 32, 6, 8, 4, 64, "fp16"),     # fused widen
    (300, 32, 6, 8, 4, 64, "int8"),     # fused per-row scales
])
def test_multiquery_slab_pallas_matches_ref(n, d, q, k, block_q, block_n,
                                            dtype):
    rng = np.random.default_rng(99)
    emb = rng.standard_normal((n, d)).astype(np.float32)
    qs = rng.standard_normal((q, d)).astype(np.float32)
    virt = _random_slab_membership(rng, n, q)
    scales = None
    if dtype == "fp16":
        emb = emb.astype(np.float16)
    elif dtype == "int8":
        emb, sc = quantize_rows(emb)
        scales = sc.astype(np.float32)
    keff = min(k, n)
    pv, pr = slab_topk_pallas(emb, qs, virt, keff, scales,
                              block_n=block_n, block_q=block_q,
                              interpret=True)
    rv, rr = slab_topk_ref(emb, qs, virt, keff, scales)
    pv, pr = np.asarray(pv), np.asarray(pr)
    rv, rr = np.asarray(rv), np.asarray(rr)
    valid = rv > -1e29               # lanes with a real candidate
    assert np.array_equal(pr[valid], rr[valid])
    np.testing.assert_allclose(pv[valid], rv[valid], atol=2e-4)
    assert (pv[~valid] <= -1e29).all()


def test_slab_ref_equals_concat_topk_oracle():
    """The (score desc, virt asc) selection == lax.top_k over each query's
    virtual concatenation — the exact contract the engine relies on."""
    rng = np.random.default_rng(3)
    n, d, nq, k = 257, 32, 7, 9
    emb = rng.standard_normal((n, d)).astype(np.float32)
    qs = rng.standard_normal((nq, d)).astype(np.float32)
    virt = _random_slab_membership(rng, n, nq)
    vals, rows = slab_topk(emb, qs, virt, k, impl="ref")
    vals, rows = np.asarray(vals), np.asarray(rows)
    for q in range(nq):
        member = np.where(virt[q] < NOT_PROBED)[0]
        order = member[np.argsort(virt[q][member])]   # virtual concat order
        if len(order) == 0:
            assert (vals[q] <= -1e29).all()
            continue
        rv, ri = topk_ip(emb[order], qs[q:q + 1], min(k, len(order)))
        rv, ri = np.asarray(rv)[0], np.asarray(ri)[0]
        kk = len(rv)
        np.testing.assert_allclose(vals[q][:kk], rv, rtol=1e-6, atol=1e-6)
        assert np.array_equal(rows[q][:kk], order[ri])


# ---------------------------------------------------------------------------
# PQ LUT scoring differentials (core/pq.py + the kernels' fourth
# representation)
# ---------------------------------------------------------------------------
def test_pq_lut_scoring_matches_decode_then_exact(ds):
    """PQ ADC scoring (ref AND Pallas) over a clustered slab: Pallas is
    bit-identical to the ref path, both agree with decode-then-fp32-exact
    scoring of the same codes to fp32 tolerance, and the selected rows
    overlap the TRUE fp32 top-k by >= 0.9 per query."""
    from repro.core.pq import pq_decode, pq_encode, pq_luts, train_pq
    emb = ds.embeddings.astype(np.float32)
    cb = train_pq(emb, m=16, iters=10, seed=3)
    codes = pq_encode(cb, emb)
    n, nq, k = emb.shape[0], 12, 10
    rng = np.random.default_rng(7)
    virt = _random_slab_membership(rng, n, nq)
    qs = ds.query_embs[:nq]
    luts = pq_luts(cb, qs)
    rv, rr = map(np.asarray, slab_topk(codes, qs, virt, k,
                                       luts=luts, impl="ref"))
    pv, pr = map(np.asarray, slab_topk_pallas(codes, qs, virt, k,
                                              None, luts, interpret=True))
    dv, dr = map(np.asarray,
                 slab_topk_ref(pq_decode(cb, codes), qs, virt, k))
    ev, er = map(np.asarray, slab_topk_ref(emb, qs, virt, k))
    valid = rv > -1e29
    assert (pv[~valid] <= -1e29).all()
    # Pallas one-hot-matmul gather == jnp take gather, bitwise
    assert np.array_equal(pr[valid], rr[valid])
    assert np.array_equal(pv[valid], rv[valid])
    # LUT accumulate == decode-then-dot on the same codes, fp32 tolerance
    np.testing.assert_allclose(rv[valid], dv[valid], atol=2e-5)
    assert np.array_equal(rr[valid], dr[valid])
    # clustered data: PQ top-k tracks the unquantized fp32 top-k
    for q in range(nq):
        truth = set(er[q][ev[q] > -1e29].tolist())
        if truth:
            got = set(rr[q][rv[q] > -1e29].tolist())
            assert len(got & truth) / len(truth) >= 0.9


def test_mixed_four_representation_slab_matches_per_segment_merge(ds):
    """A synthetic slab holding all FOUR representations at once: the
    engine's fused multi-segment scoring (slab_score_topk) is bit-identical
    to scoring each representation's segment separately and merging the
    candidates under the (score desc, virt asc) order."""
    from repro.core.edgerag import slab_score_topk
    from repro.core.pq import pq_encode, pq_luts, train_pq
    from repro.core.resolver import SlabLayout
    emb = ds.embeddings.astype(np.float32)
    cb = train_pq(emb, m=16, iters=8, seed=5)
    n, nq, k, dim = emb.shape[0], 10, 9, emb.shape[1]
    rng = np.random.default_rng(21)
    bounds = [0, *np.sort(rng.choice(np.arange(1, n), 7,
                                     replace=False)).tolist(), n]
    kinds = ["fp32", "fp16", "int8", "pq", "pq", "int8", "fp16", "fp32"]
    payloads, ids_of_map = {}, {}
    for cid, kind in enumerate(kinds):
        rows = emb[bounds[cid]:bounds[cid + 1]]
        ids_of_map[cid] = np.arange(bounds[cid], bounds[cid + 1], dtype=np.int64)
        if kind == "fp32":
            payloads[cid] = SlabPayload("fp32", rows)
        elif kind == "fp16":
            payloads[cid] = SlabPayload("fp16", rows.astype(np.float16))
        elif kind == "int8":
            q8, sc = quantize_rows(rows)
            payloads[cid] = SlabPayload("int8", q8,
                                        sc.astype(np.float32))
        else:
            payloads[cid] = SlabPayload("pq", pq_encode(cb, rows),
                                        codebook=cb)
    order = list(range(len(kinds)))
    slab = SlabLayout.pack(dim, order, payloads, lambda c: ids_of_map[c])
    assert sorted(seg.kind for seg in slab.segments) == \
        ["fp16", "fp32", "int8", "pq"]
    probed = [list(rng.permutation(len(kinds))[:int(rng.integers(1, 7))])
              for _ in range(nq)]
    qs = ds.query_embs[:nq]
    got_ids, got_vals, n_valid = slab_score_topk(slab, qs, k, probed)
    # reference: one kernel launch PER representation, then an independent
    # lexsort merge of the per-segment candidates
    virts, ref_n_valid, n_valid_seg = slab.query_layout(probed)
    cv, ct, ci = [], [], []
    lane = np.arange(k)[None, :]
    for seg in slab.segments:
        luts = pq_luts(seg.codebook, qs) if seg.kind == "pq" else None
        vals, rows = map(np.asarray, slab_topk(
            seg.emb, qs, virts[seg.kind], k, scales=seg.scales, luts=luts))
        ok = lane < n_valid_seg[seg.kind][:, None]
        rows = np.where(ok, rows, 0)
        cv.append(np.where(ok, vals, -np.inf))
        ci.append(np.where(ok, seg.ids[rows], -1))
        ct.append(np.where(ok, virts[seg.kind][np.arange(nq)[:, None], rows],
                           np.int32(NOT_PROBED)))
    cv, ct, ci = (np.concatenate(a, axis=1) for a in (cv, ct, ci))
    merge = np.lexsort((ct, -cv), axis=1)[:, :k]
    ref_vals = np.take_along_axis(cv, merge, axis=1)
    ref_ids = np.take_along_axis(ci, merge, axis=1)
    assert np.array_equal(got_vals, ref_vals)
    assert np.array_equal(got_ids, ref_ids)
    assert np.array_equal(n_valid, ref_n_valid)


def test_mixed_pq_and_fp32_batch_matches_per_query_loop(ds):
    """End-to-end mid-SLO pq-codec index: the batch slab mixes pq storage
    segments with fp32 regen/cache segments; results match the per-query
    decode-then-score loop within PQ tolerance on the scores it can
    reproduce (both paths decode the SAME codes, so ids track wherever the
    score order is pinned)."""
    nq = 12
    kw = dict(slo_s=0.1, store_heavy=True, cache_bytes=1 << 20,
              storage_codec="pq")
    slab_er = _fresh(ds, **kw)
    plan = slab_er.plan_batch(ds.query_embs[:nq], 5)
    lats = [LatencyBreakdown() for _ in range(nq)]
    probe_slab = slab_er.resolver.execute_slab(plan, lats, [False] * nq)
    kinds = sorted(seg.kind for seg in probe_slab.segments)
    assert kinds == ["fp32", "pq"], kinds
    slab_er = _fresh(ds, **kw)
    loop_er = _fresh(ds, **kw)
    s_ids, s_vals, lats = slab_er.search_batch(ds.query_embs[:nq], 10, 5)
    l_ids, l_vals = _per_query_loop(loop_er, ds.query_embs[:nq], 10, 5)
    np.testing.assert_allclose(s_vals, l_vals, atol=2e-5, rtol=1e-5)
    overlap = np.mean([len(set(s_ids[q]) & set(l_ids[q])) / 10
                       for q in range(nq)])
    assert overlap >= 0.9
    # pq cost fields charged; dequant fields untouched by pq segments
    assert sum(l.l2_pq_lut_s for l in lats) > 0
    assert sum(l.l2_pq_gather_s for l in lats) > 0
    assert sum(l.l2_fused_dequant_s for l in lats) == 0


# ---------------------------------------------------------------------------
# lazy-decay LFU == eager reference
# ---------------------------------------------------------------------------
class _EagerLFU:
    """The pre-optimization implementation: O(n) decay walk per access and
    a full byte scan per insert — the behavioral oracle."""

    def __init__(self, capacity_bytes, decay_factor):
        self.capacity = capacity_bytes
        self.f = decay_factor
        self.entries = {}            # cid -> [nbytes, gen, counter]
        self.hits = self.misses = self.evictions = 0

    def total_bytes(self):
        return sum(e[0] for e in self.entries.values())

    def access(self, cid):
        if cid in self.entries:
            self.entries[cid][2] += 1.0
            self.hits += 1
            out = True
        else:
            self.misses += 1
            out = False
        for e in self.entries.values():
            e[2] *= self.f
        return out

    def insert(self, cid, nbytes, gen, thr=0.0):
        if gen < thr or nbytes > self.capacity:
            return
        # the seed implementation overwrote WITHOUT releasing first: the
        # eviction loop counts the old entry's bytes and may evict it
        while self.total_bytes() + nbytes > self.capacity:
            if not self.entries:
                return
            victim = min(self.entries,
                         key=lambda i: (self.entries[i][1]
                                        * self.entries[i][2]))
            del self.entries[victim]
            self.evictions += 1
        self.entries[cid] = [nbytes, gen, 1.0]

    def drop_below(self, thr):
        for cid in [c for c, e in self.entries.items() if e[1] < thr]:
            del self.entries[cid]
            self.evictions += 1

    def invalidate(self, cid):
        self.entries.pop(cid, None)


def test_lazy_decay_cache_matches_eager_reference():
    """Randomized op-sequence equivalence: membership, running byte total,
    hit/miss/eviction counts all match the eager O(n)-per-access oracle."""
    rng = np.random.default_rng(7)
    cache = CostAwareLFUCache(capacity_bytes=40 * 32, decay_factor=0.9)
    ref = _EagerLFU(40 * 32, 0.9)
    for step in range(600):
        op = rng.random()
        cid = int(rng.integers(0, 30))
        if op < 0.45:
            got = cache.access(cid)
            assert (got is not None) == ref.access(cid)
        elif op < 0.8:
            n_rows = int(rng.integers(1, 9))
            emb = np.ones((n_rows, 8), np.float32)      # 32 B per row
            gen = float(rng.random() + 0.01)
            thr = float(rng.random() * 0.2)
            cache.insert(cid, emb, gen, min_latency_threshold=thr)
            ref.insert(cid, emb.nbytes, gen, thr)
        elif op < 0.9:
            cache.invalidate(cid)
            ref.invalidate(cid)
        else:
            thr = float(rng.random() * 0.3)
            cache.drop_below_threshold(thr)
            ref.drop_below(thr)
        assert set(cache._entries) == set(ref.entries), step
        assert cache.total_bytes() == ref.total_bytes(), step
        assert (cache.hits, cache.misses, cache.evictions) == \
            (ref.hits, ref.misses, ref.evictions), step
