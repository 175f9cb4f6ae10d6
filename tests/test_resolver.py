"""Tiered cluster-resolution pipeline: ResolutionPlan structure, plan-driven
search parity with sequential search, precomputed-plan execution, coalesced
regeneration groups, the engine's answer wrapper + prefetch overlap, and the
sharded scoring route."""
import jax
import numpy as np
import pytest

from repro.core import EdgeCostModel, EdgeRAGIndex
from repro.core.resolver import TIER_CACHE, TIER_REGEN, TIER_STORAGE
from repro.data import generate_dataset
from repro.serving.engine import RAGEngine

pytestmark = pytest.mark.fast


@pytest.fixture(scope="module")
def ds():
    return generate_dataset(n_records=900, dim=32, n_topics=30,
                            n_queries=48, seed=7)


def _fresh(ds, **kw):
    kw.setdefault("slo_s", 0.15)
    er = EdgeRAGIndex(32, ds.embedder, ds.get_chunks, EdgeCostModel(), **kw)
    er.build(ds.chunk_ids, ds.texts, nlist=30, embeddings=ds.embeddings,
             seed=1)
    return er


def test_plan_driven_batch_matches_sequential_search(ds):
    """Acceptance: ResolutionPlan-driven search_batch equals the sequential
    per-query search on ids exactly and on scores to f32 rounding (fp32
    tier; Q=1 and Q=20 matmuls reduce in different orders)."""
    seq = _fresh(ds, cache_bytes=1 << 20)
    bat = _fresh(ds, cache_bytes=1 << 20)
    nq = 20
    s_ids, s_vals = [], []
    for qi in range(nq):
        ids, vals, _ = seq.search(ds.query_embs[qi], 10, 5)
        s_ids.append(ids[0])
        s_vals.append(vals[0])
    b_ids, b_vals, _ = bat.search_batch(ds.query_embs[:nq], 10, 5)
    assert np.array_equal(np.stack(s_ids), b_ids)
    np.testing.assert_allclose(np.stack(s_vals), b_vals, rtol=1e-6, atol=1e-6)


def test_plan_structure(ds):
    """Tier assignment: stored clusters -> storage; unknown -> regen on the
    first batch, then cache on the second.  Owner is the lowest-index
    query; every probed cluster is planned exactly once."""
    er = _fresh(ds, cache_bytes=8 << 20)
    plan = er.plan_batch(ds.query_embs[:12], 5)
    assert plan.n_unique == len(plan.tier) == len(plan.owner)
    assert set(plan.tier) == {c for p in plan.probed_per_q for c in p}
    for cid, t in plan.tier.items():
        stored = er.clusters[cid].stored
        assert t == (TIER_STORAGE if stored else TIER_REGEN)
        assert plan.owner[cid] == min(
            qi for qi, p in enumerate(plan.probed_per_q) if cid in p)
    assert set(plan.storage_clusters) == {
        c for c, t in plan.tier.items() if t == TIER_STORAGE}
    # all regens coalesce into ONE group by default
    assert len(plan.regen_groups) <= 1
    assert set(plan.regen_clusters) == {
        c for c, t in plan.tier.items() if t == TIER_REGEN}
    # execute the plan, then re-plan: regenerated clusters now hit the cache
    er.search_batch(ds.query_embs[:12], 10, 5, plan=plan)
    plan2 = er.plan_batch(ds.query_embs[:12], 5)
    for cid in plan.regen_clusters:
        assert plan2.tier[cid] == TIER_CACHE


def test_precomputed_plan_matches_inline(ds):
    """search_batch(plan=plan_batch(...)) is byte-for-byte the inline path
    (ids, scores, every LatencyBreakdown field except wall time)."""
    a = _fresh(ds, cache_bytes=1 << 20)
    b = _fresh(ds, cache_bytes=1 << 20)
    nq = 16
    ids_a, vals_a, lats_a = a.search_batch(ds.query_embs[:nq], 10, 5)
    plan = b.plan_batch(ds.query_embs[:nq], 5)
    ids_b, vals_b, lats_b = b.search_batch(ds.query_embs[:nq], 10, 5,
                                           plan=plan)
    assert np.array_equal(ids_a, ids_b)
    assert np.array_equal(vals_a, vals_b)
    for la, lb in zip(lats_a, lats_b):
        da, db = la.as_dict(), lb.as_dict()
        for key in da:
            if key != "wall_s":
                assert da[key] == db[key], key


def test_regen_group_budget(ds):
    """max_group_chars splits the coalesced regeneration into multiple
    embed_fn calls without changing results."""
    a = _fresh(ds, store_heavy=False, cache_bytes=0)
    b = _fresh(ds, store_heavy=False, cache_bytes=0)
    b.resolver.max_group_chars = 1          # one call per cluster
    nq = 8
    calls0 = ds.embedder.calls
    ids_a, vals_a, _ = a.search_batch(ds.query_embs[:nq], 10, 5)
    one_call = ds.embedder.calls - calls0
    assert one_call == 1
    calls0 = ds.embedder.calls
    ids_b, vals_b, lats = b.search_batch(ds.query_embs[:nq], 10, 5)
    assert ds.embedder.calls - calls0 == sum(l.n_generated for l in lats)
    assert np.array_equal(ids_a, ids_b)
    assert np.array_equal(vals_a, vals_b)


def test_answer_is_thin_wrapper_over_answer_batch(ds):
    """RAGEngine.answer == answer_batch with a batch of one."""
    ea = RAGEngine(_fresh(ds, cache_bytes=1 << 20), None, k=5, nprobe=4)
    eb = RAGEngine(_fresh(ds, cache_bytes=1 << 20), None, k=5, nprobe=4)
    for qi in range(5):
        q = f"query number {qi}"
        ra = ea.answer(q, ds.query_embs[qi], ds.get_chunks)
        rb = eb.answer_batch([q], ds.query_embs[qi][None], ds.get_chunks)[0]
        assert ra.chunk_ids == rb.chunk_ids
        assert ra.context == rb.context
        assert ra.ttft_edge_s == rb.ttft_edge_s
        assert ra.prefill_edge_s == rb.prefill_edge_s
        da, db = ra.retrieval.as_dict(), rb.retrieval.as_dict()
        for key in da:
            if key != "wall_s":
                assert da[key] == db[key], key


def test_prefetch_overlaps_storage_io(ds):
    """answer_batch(prefetch=True): identical retrieval, smaller edge TTFT —
    the plan's storage loads run under the rest of retrieval."""
    base = RAGEngine(_fresh(ds, slo_s=0.05, cache_bytes=0), None,
                     k=5, nprobe=4)
    pre = RAGEngine(_fresh(ds, slo_s=0.05, cache_bytes=0), None,
                    k=5, nprobe=4)
    queries = [f"query {i}" for i in range(8)]
    r0 = base.answer_batch(queries, ds.query_embs[:8], ds.get_chunks)
    r1 = pre.answer_batch(queries, ds.query_embs[:8], ds.get_chunks,
                          prefetch=True)
    assert any(r.retrieval.n_storage_loads > 0 for r in r0)
    saved_total = 0.0
    for a, b in zip(r0, r1):
        assert a.chunk_ids == b.chunk_ids
        assert a.context == b.context
        assert b.prefetch_saved_s >= 0.0
        assert b.ttft_edge_s == pytest.approx(
            a.ttft_edge_s - b.prefetch_saved_s)
        saved_total += b.prefetch_saved_s
    assert saved_total > 0.0


def test_prefetched_plan_survives_storage_delete(ds):
    """A storage key deleted between prefetch and execute falls back to
    regeneration — even though the stale payload was already prefetched."""
    ref = _fresh(ds, slo_s=0.05, cache_bytes=0)
    er = _fresh(ds, slo_s=0.05, cache_bytes=0)
    plan = er.plan_batch(ds.query_embs[:6], 5, prefetch_storage=True)
    assert plan.storage_clusters and plan.prefetched
    for cid in plan.storage_clusters:
        er.storage.delete(cid)
    ids, vals, lats = er.search_batch(ds.query_embs[:6], 10, 5, plan=plan)
    r_ids, r_vals, _ = ref.search_batch(ds.query_embs[:6], 10, 5)
    assert np.array_equal(ids, r_ids)
    assert np.array_equal(vals, r_vals)
    assert sum(l.n_storage_loads for l in lats) == 0
    assert sum(l.n_generated for l in lats) >= len(plan.storage_clusters)
    # self-heal: the vanished storage copies were re-persisted, so the next
    # batch loads instead of regenerating forever
    assert all(cid in er.storage for cid in plan.storage_clusters)
    _, _, lats2 = er.search_batch(ds.query_embs[:6], 10, 5)
    assert sum(l.n_storage_loads for l in lats2) == len(plan.storage_clusters)
    assert sum(l.n_generated for l in lats2) == len(plan.regen_clusters)


def test_stale_cached_plan_payload_falls_back(ds):
    """A cluster mutated between plan and execute invalidates the plan's
    cached payload (size guard) — the cluster regenerates instead of
    scoring a misaligned id map."""
    er = _fresh(ds, store_heavy=False, cache_bytes=8 << 20)
    er.search_batch(ds.query_embs[:6], 10, 5)       # populate the cache
    plan = er.plan_batch(ds.query_embs[:6], 5)
    assert plan.cached
    victim = next(iter(plan.cached))
    new_id = 900_001
    text = "fresh chunk " * 30
    ds.add_chunk(new_id, text, ds.embeddings[0])
    cl = er.clusters[victim]                        # mutate cluster directly
    cl.ids = np.append(cl.ids, np.int64(new_id))
    cl.char_count += len(text)
    er._chunk_chars[new_id] = len(text)
    er._chunk_cluster[new_id] = victim
    ids, vals, lats = er.search_batch(ds.query_embs[:6], 10, 5, plan=plan)
    fresh = _fresh(ds, store_heavy=False, cache_bytes=0)
    fresh.clusters[victim].ids = er.clusters[victim].ids.copy()
    f_ids, f_vals, _ = fresh.search_batch(ds.query_embs[:6], 10, 5)
    assert np.array_equal(ids, f_ids)
    assert np.array_equal(vals, f_vals)
    assert sum(l.n_generated for l in lats) >= 1    # victim regenerated
    # the stale entry was invalidated and replaced, not left to recur
    cached_now = er.cache.access(victim)
    assert cached_now is not None
    assert len(cached_now) == er.clusters[victim].size


def test_sharded_scoring_route_single_device(ds):
    """search_batch(mesh=...) routes scoring through sharded_slab_topk and
    matches the unsharded ids (1-device mesh; the 8-device equivalence runs
    in test_sharded_retrieval.py's subprocess)."""
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    a = _fresh(ds, cache_bytes=1 << 20)
    b = _fresh(ds, cache_bytes=1 << 20)
    ids_a, _, _ = a.search_batch(ds.query_embs[:8], 10, 5)
    ids_b, _, lats = b.search_batch(ds.query_embs[:8], 10, 5, mesh=mesh)
    assert np.array_equal(ids_a, ids_b)
    assert all(l.l2_search_s > 0 for l in lats if l.n_clusters_probed)
