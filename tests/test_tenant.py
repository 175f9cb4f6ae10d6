"""TenantRouter: shared-substrate multi-tenancy.

The contract under test: a one-tenant router replays a standalone
EdgeRAGIndex EXACTLY (ids, scores, modeled charges, Alg. 3 state); a
mixed-tenant fused batch is bitwise identical to serving each tenant's
queries through its own silo; tenants are isolated on the shared storage /
cache / maintenance substrate; and the serving layer (RAGEngine,
StagedPipeline, RequestScheduler + TokenBucketAdmission) threads tenancy
end to end."""
import numpy as np
import pytest

from repro.core import EdgeCostModel, EdgeRAGIndex, TenantRouter
from repro.core.maintenance import (FairShareMaintenance,
                                    MaintenanceScheduler)
from repro.data import generate_dataset
from repro.serving.engine import RAGEngine
from repro.serving.pipeline import PipelineBatch, StagedPipeline
from repro.serving.scheduler import RequestScheduler, TokenBucketAdmission

pytestmark = pytest.mark.fast

DIM = 32
K = 5
NPROBE = 3
CACHE = 1 << 20


@pytest.fixture(scope="module")
def corpora():
    return [generate_dataset(n_records=360, dim=DIM, n_topics=8,
                             n_queries=6, seed=40 + t)
            for t in range(3)]


def _cost():
    return EdgeCostModel()


def _standalone(ds, cost, nlist=10, slo_s=0.002, cache_bytes=CACHE):
    ix = EdgeRAGIndex(DIM, ds.embedder, ds.get_chunks, cost, slo_s=slo_s,
                      cache_bytes=cache_bytes, maintenance="deferred")
    ix.build(ds.chunk_ids, ds.texts, nlist=nlist, embeddings=ds.embeddings,
             seed=1)
    return ix


def _router(corpora, cost, nlist=10, slo_s=0.002):
    router = TenantRouter(DIM, cost, slo_s=slo_s, cache_bytes=CACHE)
    for t, ds in enumerate(corpora):
        ix = router.create_tenant(f"t{t}", ds.embedder, ds.get_chunks)
        ix.build(ds.chunk_ids, ds.texts, nlist=nlist,
                 embeddings=ds.embeddings, seed=1)
    return router


# ----------------------------------------------------------------------
# bit-identity
# ----------------------------------------------------------------------
def test_one_tenant_router_matches_standalone(corpora):
    """Same kernel calls, same cache/threshold mutations, same modeled
    charges — cold AND warm passes."""
    ds = corpora[0]
    cost = _cost()
    sa = _standalone(ds, cost)
    router = _router(corpora[:1], cost)
    tix = router.tenant("t0")
    qc = [int(c) for c in ds.query_chars]
    for _ in range(3):
        ids0, vals0, lats0 = sa.search_batch(ds.query_embs, K, NPROBE, qc)
        ids1, vals1, lats1 = router.search_batch(ds.query_embs, K, NPROBE,
                                                 qc, tenants="t0")
        np.testing.assert_array_equal(ids0, ids1)
        np.testing.assert_array_equal(vals0, vals1)
        for l0, l1 in zip(lats0, lats1):
            assert l0.retrieval_s == l1.retrieval_s
            assert l0.n_shared_hits == l1.n_shared_hits
            assert l0.centroid_search_s == l1.centroid_search_s
    assert sa.threshold.threshold == tix.threshold.threshold
    assert sa.cache.hit_rate == tix.cache.hit_rate
    assert sa.memory_bytes() == router.memory_bytes()


def test_mixed_batch_fused_matches_silos(corpora):
    """Interleaved 3-tenant batch through ONE fused slab launch ==
    serving each tenant's queries through its own standalone index: ids
    exactly, scores to f32 rounding (the batch shapes differ)."""
    cost = _cost()
    router = _router(corpora, cost)
    silos = [_standalone(ds, cost, cache_bytes=CACHE) for ds in corpora]
    # interleave: t0 q0, t1 q0, t2 q0, t0 q1, ...
    tenants, embs, local = [], [], []
    for qi in range(4):
        for t in range(3):
            tenants.append(f"t{t}")
            embs.append(corpora[t].query_embs[qi])
            local.append((t, qi))
    embs = np.stack(embs)
    for _ in range(2):                      # cold + warm
        mids, mvals, mlats = router.search_batch(embs, K, NPROBE,
                                                 tenants=tenants)
        refs = [silo.search_batch(ds.query_embs[:4], K, NPROBE)
                for silo, ds in zip(silos, corpora)]
        for gqi, (t, qi) in enumerate(local):
            np.testing.assert_array_equal(mids[gqi], refs[t][0][qi])
            np.testing.assert_allclose(mvals[gqi], refs[t][1][qi],
                                       rtol=1e-6, atol=1e-6)


def test_cross_tenant_plan_keys_are_tenant_scoped(corpora):
    cost = _cost()
    router = _router(corpora, cost)
    state = router.search_begin(
        np.stack([corpora[0].query_embs[0], corpora[1].query_embs[0]]),
        K, NPROBE, tenants=["t0", "t1"])
    assert all(isinstance(k, tuple) and k[0] in ("t0", "t1")
               for k in state.plan.owner)
    # no cluster key can be owned by the wrong tenant's query
    for qi, probed in enumerate(state.plan.probed_per_q):
        assert all(key[0] == state.tenants[qi] for key in probed)


# ----------------------------------------------------------------------
# shared-substrate isolation
# ----------------------------------------------------------------------
def test_storage_isolation_and_budget(corpora):
    cost = _cost()
    # slo_s=0 forces every cluster heavy => everything goes to storage
    router = TenantRouter(DIM, cost, slo_s=0.0, cache_bytes=CACHE)
    for t, ds in enumerate(corpora[:2]):
        ix = router.create_tenant(f"t{t}", ds.embedder, ds.get_chunks,
                                  slo_s=0.0)
        ix.build(ds.chunk_ids, ds.texts, nlist=8,
                 embeddings=ds.embeddings, seed=1)
    b0 = router.storage.tenant_bytes("t0")
    b1 = router.storage.tenant_bytes("t1")
    assert b0 > 0 and b1 > 0
    assert router.storage.total_bytes() == b0 + b1
    # clearing one tenant's view must not touch the other's blobs
    router.tenant("t0").storage.clear()
    assert router.storage.tenant_bytes("t0") == 0
    assert router.storage.tenant_bytes("t1") == b1


def test_shared_cache_per_tenant_accounting(corpora):
    cost = _cost()
    # high SLO: no cluster is stored, every miss regenerates + caches
    router = _router(corpora[:2], cost, slo_s=10.0)
    for rep in range(2):
        for t, ds in enumerate(corpora[:2]):
            router.search_batch(ds.query_embs, K, NPROBE,
                                tenants=f"t{t}")
    pt = router.cache.per_tenant
    for t in ("t0", "t1"):
        view = router.tenant(f"t{t[-1]}").cache
        assert view.hits == pt[t]["hits"]
        assert view.misses == pt[t]["misses"]
    assert (router.cache.hits ==
            sum(st["hits"] for st in pt.values()))
    assert (router.cache.total_bytes() ==
            sum(st["bytes"] for st in pt.values()))


def test_duplicate_and_invalid_tenant_ids(corpora):
    router = TenantRouter(DIM, _cost())
    ds = corpora[0]
    router.create_tenant("a", ds.embedder, ds.get_chunks)
    with pytest.raises(AssertionError):
        router.create_tenant("a", ds.embedder, ds.get_chunks)
    with pytest.raises(AssertionError):
        router.create_tenant("bad/id", ds.embedder, ds.get_chunks)
    with pytest.raises(AssertionError):
        router.search_begin(ds.query_embs[:1], K, NPROBE,
                            tenants=["nope"])


# ----------------------------------------------------------------------
# fair-share maintenance
# ----------------------------------------------------------------------
class _StubIndex:
    """Minimal index for MaintenanceScheduler: one drop_store per cid."""

    dim = 8

    def __init__(self):
        self.cost = EdgeCostModel()
        self.dropped = []
        self.clusters = {}

    def add(self, cid):
        import dataclasses

        @dataclasses.dataclass
        class _Cl:
            generation: int = 0
            active: bool = True
            size: int = 1
            char_count: int = 10
            stored: bool = True
            stored_generation: int = 0
            gen_latency_est: float = 0.0
        self.clusters[cid] = _Cl()

    @property
    def store_heavy(self):
        return True

    @property
    def slo_s(self):
        return 1.0      # gen_latency_est < slo -> revalidates to drop_store

    def _drop_stored(self, cid):
        self.dropped.append(cid)
        self.clusters[cid].stored = False


def test_fair_share_round_robin_alternates():
    """A churn-heavy tenant cannot starve others: execution order
    alternates tenants even when one queue is much longer."""
    fair = FairShareMaintenance()
    stubs = {}
    for t, n_ops in (("heavy", 6), ("light", 2)):
        stub = _StubIndex()
        sched = MaintenanceScheduler(stub)
        for cid in range(n_ops):
            stub.add(cid)
            sched.enqueue("drop_store", cid)
        fair.register(t, sched)
        stubs[t] = stub
    assert len(fair) == 8
    report = fair.drain(None)
    assert len(report.executed) == 8
    order = [key[1][0] for key in report.executed]
    # both of light's ops ran within the first four turns
    assert order[:4].count("light") == 2
    assert len(fair) == 0
    assert fair.stats()["light"]["fair_share_edge_s"] >= 0.0


def test_fair_share_cursor_persists_across_drains():
    fair = FairShareMaintenance()
    for t in ("a", "b"):
        stub = _StubIndex()
        sched = MaintenanceScheduler(stub)
        for cid in range(2):
            stub.add(cid)
            sched.enqueue("drop_store", cid)
        fair.register(t, sched)
    first = fair.drain(1e-12)        # tiny budget: one op (first is free)
    assert len(first.executed) == 1
    second = fair.drain(1e-12)
    assert len(second.executed) == 1
    # the second drain resumed the rotation, not restarted it
    assert first.executed[0][1][0] != second.executed[0][1][0]


def test_router_maintenance_is_fair_share(corpora):
    router = _router(corpora[:2], _cost())
    assert isinstance(router.maintenance, FairShareMaintenance)
    ds = corpora[0]
    tix = router.tenant("t0")
    # an online insert enqueues deferred work under this tenant
    n0 = len(router.maintenance)
    text = "doc-10000 " + "tok " * 20
    rng = np.random.default_rng(7)
    emb = rng.standard_normal(DIM).astype(np.float32)
    emb /= np.linalg.norm(emb)
    ds.add_chunk(10_000, text, emb)
    tix.insert(10_000, text)
    assert len(router.maintenance) >= n0
    router.maintenance.drain(None)
    assert len(router.maintenance) == 0


# ----------------------------------------------------------------------
# serving integration
# ----------------------------------------------------------------------
def test_router_through_engine_and_pipeline(corpora):
    cost = _cost()
    router = _router(corpora, cost)
    eng = RAGEngine(router, None, cost_model=cost, k=K, nprobe=NPROBE,
                    maintenance_owner="external")
    tenants = ["t0", "t1", "t2", "t0"]
    embs = np.stack([corpora[0].query_embs[0], corpora[1].query_embs[0],
                     corpora[2].query_embs[0], corpora[0].query_embs[1]])
    resp = eng.answer_batch(["q"] * 4, embs, tenants=tenants)
    assert len(resp) == 4
    # contexts come from each query's own tenant corpus
    for r, t in zip(resp, tenants):
        ds = corpora[int(t[1])]
        assert all(c in ds.texts for c in r.context)
    pipe = StagedPipeline(eng, None)
    responses, trace = pipe.run([
        PipelineBatch(queries=["q"] * 4, query_embs=embs, arrival_s=0.0,
                      tenants=tenants),
        PipelineBatch(queries=["q"] * 4, query_embs=embs, arrival_s=1e-4,
                      tenants=list(reversed(tenants)))])
    assert len(responses) == 2 and all(len(b) == 4 for b in responses)
    assert trace.stages["s4"].n_fired == 2


def test_run_pipelined_threads_tenants(corpora):
    cost = _cost()
    router = _router(corpora[:2], cost)
    eng = RAGEngine(router, None, cost_model=cost, k=K, nprobe=NPROBE,
                    maintenance_owner="external")
    pipe = StagedPipeline(eng, None)
    sched = RequestScheduler()
    for i in range(8):
        t = f"t{i % 2}"
        ds = corpora[i % 2]
        sched.submit(i * 1e-3, query="q", query_emb=ds.query_embs[i % 4],
                     slo_s=100.0, tenant=t)
    done = sched.run_pipelined(pipe, batch_size=4)
    assert len(done) == 8
    assert all(r.outcome == "met" for r in done)
    assert len(sched.pipeline_responses) == 8


# ----------------------------------------------------------------------
# admission control
# ----------------------------------------------------------------------
def test_admission_rejects_over_share_under_backlog():
    adm = TokenBucketAdmission(rate_per_s=1.0, burst=1.0)
    sched = RequestScheduler(admission=adm)
    for i in range(10):
        sched.submit(i * 0.01, slo_s=100.0, tenant="x")   # 100 req/s burst
    done = sched.run(lambda req: 0.5)                     # service 0.5 s
    counts = sched.outcome_counts()
    assert counts["rejected"] > 0
    assert counts["met"] >= 1
    rejected = [r for r in done if r.rejected]
    assert all(r.outcome == "rejected" and not r.slo_met for r in rejected)
    assert all(r.finish_s == r.start_s for r in rejected)


def test_admission_work_conserving_when_idle():
    """Sparse arrivals never queue: fair share must not bind on an idle
    device even with an empty bucket."""
    adm = TokenBucketAdmission(rate_per_s=0.001, burst=1.0)
    sched = RequestScheduler(admission=adm)
    for i in range(5):
        sched.submit(i * 10.0, slo_s=100.0, tenant="x")   # far apart
    done = sched.run(lambda req: 0.5)
    assert all(r.outcome == "met" for r in done)


def test_admission_sheds_blown_deadline():
    """A request whose queue wait alone exceeds its SLO is shed even
    with tokens available."""
    adm = TokenBucketAdmission(rate_per_s=100.0, burst=10.0)
    sched = RequestScheduler(admission=adm)
    for i in range(6):
        sched.submit(i * 0.01, slo_s=0.2, tenant="x")
    done = sched.run(lambda req: 1.0)       # each service blows the next SLO
    assert sum(r.rejected for r in done) > 0
    assert sum(adm.blown.values()) > 0


def test_admission_degrade_mode_flags_not_rejects():
    adm = TokenBucketAdmission(rate_per_s=1.0, burst=1.0, mode="degrade")
    sched = RequestScheduler(admission=adm)
    for i in range(10):
        sched.submit(i * 0.01, slo_s=100.0, tenant="x")
    done = sched.run(lambda req: 0.5)
    assert sched.outcome_counts()["rejected"] == 0
    assert any(r.pre_degraded for r in done)


def test_admission_protects_small_tenant():
    """Noisy neighbor: with per-tenant fair share, the small tenant's
    served tail collapses versus no admission."""
    def run_arm(admission):
        sched = RequestScheduler(admission=admission)
        for i in range(120):                  # big floods at 3x capacity
            sched.submit(i / 30.0, slo_s=1.0, tenant="big")
        for j in range(12):                   # small trickles
            sched.submit(j * 1.0, slo_s=1.0, tenant="small")
        sched.run(lambda req: 0.1)
        small = [r.latency_s for r in sched.completed
                 if r.tenant == "small" and not r.rejected]
        return float(np.percentile(small, 99))

    p99_off = run_arm(None)
    p99_on = run_arm(TokenBucketAdmission(rate_per_s=5.0, burst=2.0))
    assert p99_on < p99_off


def test_router_stats_shape(corpora):
    router = _router(corpora[:2], _cost())
    router.search_batch(corpora[0].query_embs[:2], K, NPROBE, tenants="t0")
    st = router.stats()
    assert st["n_tenants"] == 2
    assert set(st["tenants"]) == {"t0", "t1"}
    assert st["cache"]["capacity_bytes"] == CACHE
    assert "t0" in st["storage"]["per_tenant"]
    assert st["memory_bytes"] == router.memory_bytes()
