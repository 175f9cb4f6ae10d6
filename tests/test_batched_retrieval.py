"""Batched retrieval fast path: search_batch parity with sequential search
across the Table-4 ablation configs, coalesced-embed call counting, batch
cache/threshold semantics, the chunk->cluster map, and the multi-query
Pallas kernel."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EdgeCostModel, EdgeRAGIndex
from repro.core.cache_policy import MinLatencyThresholdController
from repro.data import generate_dataset
from repro.kernels.ivf_topk.kernel import topk_ip_pallas
from repro.kernels.ivf_topk.ref import topk_ip_ref
from repro.serving.engine import RAGEngine

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
                            n_queries=64, seed=5)


def _fresh(ds, **kw):
    er = EdgeRAGIndex(32, ds.embedder, ds.get_chunks, EdgeCostModel(),
                      slo_s=0.3, **kw)
    er.build(ds.chunk_ids, ds.texts, nlist=30, embeddings=ds.embeddings,
             seed=1)
    return er


@pytest.mark.parametrize("cfg", list(CONFIGS))
def test_search_batch_bit_identical_to_sequential(ds, cfg):
    """ids from one search_batch == per-query search loop exactly, scores to
    f32 rounding (Q=1 and Q=24 matmuls reduce in different orders), for
    every Table-4 ablation config."""
    seq = _fresh(ds, **CONFIGS[cfg])
    bat = _fresh(ds, **CONFIGS[cfg])
    nq = 24
    s_ids, s_vals = [], []
    for qi in range(nq):
        ids, vals, _ = seq.search(ds.query_embs[qi], 10, 5)
        s_ids.append(ids[0])
        s_vals.append(vals[0])
    b_ids, b_vals, lats = bat.search_batch(ds.query_embs[:nq], 10, 5)
    assert np.array_equal(np.stack(s_ids), b_ids)
    np.testing.assert_allclose(np.stack(s_vals), b_vals, rtol=1e-6, atol=1e-6)
    assert len(lats) == nq
    # dedup really happened: Zipf queries share clusters
    assert sum(l.n_shared_hits for l in lats) > 0


def test_search_batch_single_coalesced_embed_call(ds):
    """All cache-miss regenerations in a batch coalesce into EXACTLY one
    embed_fn call (acceptance criterion)."""
    er = _fresh(ds, **CONFIGS["embed_gen"])   # every probe regenerates
    for nq in (4, 16):
        calls0 = ds.embedder.calls
        _, _, lats = er.search_batch(ds.query_embs[:nq], 10, 5)
        assert ds.embedder.calls - calls0 == 1
        assert sum(l.n_generated for l in lats) > 1   # many clusters, 1 call


def test_search_is_degenerate_batch(ds):
    """The single-query wrapper is a batch of one: results and the full
    LatencyBreakdown agree field for field."""
    a = _fresh(ds, **CONFIGS["edgerag"])
    b = _fresh(ds, **CONFIGS["edgerag"])
    for qi in range(6):
        ids_a, vals_a, lat_a = a.search(ds.query_embs[qi], 10, 5,
                                        query_chars=50)
        ids_b, vals_b, lats_b = b.search_batch(
            ds.query_embs[qi][None], 10, 5, query_chars=[50])
        assert np.array_equal(ids_a, ids_b)
        assert np.array_equal(vals_a, vals_b)
        da, db = lat_a.as_dict(), lats_b[0].as_dict()
        for key in da:
            if key == "wall_s":
                continue
            assert da[key] == db[key], key


def test_batch_cache_and_threshold_semantics(ds):
    """Documented batch semantics: the cache is consulted once per unique
    cluster, every regenerated cluster admitted under the threshold is
    cached, and the Alg. 3 controller observes once per (non-empty) query
    in batch order."""
    er = _fresh(ds, **CONFIGS["edgerag"])
    misses0, hits0 = er.cache.misses, er.cache.hits
    nq = 16
    _, _, lats = er.search_batch(ds.query_embs[:nq], 10, 5)
    uniq_regen = sum(l.n_generated for l in lats)
    uniq_hit = sum(l.n_cache_hits for l in lats)
    # one cache access per unique non-stored cluster
    assert er.cache.misses - misses0 == uniq_regen
    assert er.cache.hits - hits0 == uniq_hit
    # replay the controller: one observation per query, misses flagged on
    # owners of regenerated clusters
    ctrl = MinLatencyThresholdController()
    for lat in lats:
        if lat.n_clusters_probed == 0:
            continue
        ctrl.observe(lat.n_generated > 0, lat.retrieval_s)
    assert er.threshold.threshold == pytest.approx(ctrl.threshold)
    assert er.threshold.moving_avg_latency == pytest.approx(
        ctrl.moving_avg_latency)


def test_latency_attribution_shared_clusters(ds):
    """Owner pays resolution; peers record shared DRAM hits; counters add
    up per query."""
    er = _fresh(ds, **CONFIGS["embed_gen"])
    q = np.stack([ds.query_embs[0]] * 4)      # identical queries: max overlap
    _, _, lats = er.search_batch(q, 10, 5)
    # owner (first query) resolved everything
    assert lats[0].n_generated == lats[0].n_clusters_probed
    assert lats[0].n_shared_hits == 0
    for lat in lats[1:]:
        assert lat.n_generated == 0
        assert lat.n_shared_hits == lat.n_clusters_probed
        assert lat.l2_mem_load_s > 0
    for lat in lats:
        assert (lat.n_generated + lat.n_storage_loads + lat.n_cache_hits
                + lat.n_shared_hits == lat.n_clusters_probed)


def test_chunk_cluster_map_consistency(ds):
    """The chunk->cluster map survives insert / remove / split / merge and
    always matches a recomputed ground truth."""
    er = _fresh(ds, split_max_chars=4000, merge_min_size=2)

    def check():
        truth = {}
        for cid, cl in enumerate(er.clusters):
            if not cl.active:
                continue
            for i in cl.ids:
                truth[int(i)] = cid
        assert er._chunk_cluster == truth

    check()
    rng = np.random.default_rng(0)
    next_id = 900_000
    live = [int(i) for i in ds.chunk_ids]
    for step in range(40):
        if step % 3 != 2:
            emb = ds.embeddings[int(rng.integers(ds.n))]
            text = f"doc-{next_id} " + "pad " * int(rng.integers(10, 200))
            ds.add_chunk(next_id, text, emb)
            er.insert(next_id, text)
            live.append(next_id)
            next_id += 1
        else:
            victim = live.pop(int(rng.integers(len(live))))
            assert er.remove(victim) is not None
        check()
    assert er.remove(123_456_789) is None     # unknown id


def test_answer_batch_matches_answer(ds):
    """Sim-only serving: answer_batch returns the same contexts as
    per-query answer."""
    seq_engine = RAGEngine(_fresh(ds, **CONFIGS["edgerag"]), None,
                           k=5, nprobe=4)
    bat_engine = RAGEngine(_fresh(ds, **CONFIGS["edgerag"]), None,
                           k=5, nprobe=4)
    queries = [f"query number {i}" for i in range(8)]
    singles = [seq_engine.answer(q, ds.query_embs[i], ds.get_chunks)
               for i, q in enumerate(queries)]
    batched = bat_engine.answer_batch(queries, ds.query_embs[:8],
                                      ds.get_chunks)
    assert len(batched) == 8
    for s, b in zip(singles, batched):
        assert s.chunk_ids == b.chunk_ids
        assert s.context == b.context
        assert b.ttft_edge_s > 0


@pytest.mark.slow
def test_answer_batch_with_continuous_batcher(ds):
    """Retrieval batching composes with decode batching: answer_batch feeds
    prompts through ContinuousBatcher.admit and every query gets tokens."""
    import jax
    from repro import configs
    from repro.models import model as M
    from repro.serving.batching import ContinuousBatcher

    cfg = configs.get_config("stablelm-1.6b").reduced(num_layers=1,
                                                      d_model=64)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batcher = ContinuousBatcher(cfg, params, num_slots=2, max_len=64)
    engine = RAGEngine(_fresh(ds, **CONFIGS["edgerag"]), None,
                       k=4, nprobe=3, max_new_tokens=3)
    queries = [f"query {i}" for i in range(5)]
    responses = engine.answer_batch(queries, ds.query_embs[:5],
                                    ds.get_chunks, batcher=batcher)
    assert len(responses) == 5
    for r in responses:
        assert len(r.output_tokens) == 3
        assert r.chunk_ids and r.decode_wall_s > 0


# ---------------------------------------------------------------------------
# multi-query Pallas kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,d,q,k,block_q,block_n", [
    (300, 32, 17, 7, 8, 64),     # q not a block_q multiple (padded)
    (64, 32, 16, 9, 8, 64),      # exact tiles
    (130, 128, 5, 10, 4, 64),    # both axes padded
    (512, 64, 1, 5, 8, 128),     # single query, degenerate block
    (33, 32, 9, 33, 8, 32),      # k == n
])
def test_multiquery_pallas_matches_ref(n, d, q, k, block_q, block_n):
    rng = np.random.default_rng(1234)
    embs = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    qs = jnp.asarray(rng.standard_normal((q, d)), jnp.float32)
    keff = min(k, n)
    pv, pi = topk_ip_pallas(embs, qs, keff, block_n=block_n,
                            block_q=block_q, interpret=True)
    rv, ri = topk_ip_ref(embs, qs, keff)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(rv), atol=2e-4)
    assert (np.asarray(pi) == np.asarray(ri)).all()
