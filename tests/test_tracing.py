"""The program's spans (``repro.core.tracing``): wall time with tracing
off, records with parents and request ids over one served batch, the
profiler annotation, and the engine's first-token time."""
import glob
import time

import pytest

from repro.core import tracing

# every span the served path opens, from the query embedding to the token
SERVED_SPANS = ("embed.tokenize", "embed.encode", "rag.answer_batch",
                "s1.stage", "s1.begin", "s1.probe", "s1.tier_plan",
                "s2.stage", "s2.resolve", "s2.storage_read", "s2.regen",
                "s3.stage", "s3.finish", "s3.slab_kernel", "s3.alg3",
                "s3.prompt", "s4.answer", "s4.tokenize", "s4.kv_init",
                "s4.prefill", "s4.decode", "s4.decode_step", "s4.read_tokens")
MAX_NEW_TOKENS = 3


@pytest.fixture
def recording():
    tracing.reset()
    tracing.enable()
    yield
    tracing.disable()
    tracing.reset()


def test_disabled_span_times_and_records_nothing():
    tracing.reset()
    with tracing.span("s1.probe", rows=3) as sp:
        time.sleep(0.01)
        sp.note(clusters=2)
    assert sp.elapsed >= 0.01 and sp.end - sp.start == sp.elapsed
    assert tracing.records() == []


def test_records_nest_and_carry_request_ids(recording):
    with tracing.span("rag.answer_batch", batch=7, queries=2):
        with tracing.span("s1.probe", rows=2) as sp:
            sp.note(clusters=3)
        with tracing.span("s4.answer", query=1):
            with tracing.span("s4.prefill"):
                pass
    with tracing.span("embed.encode"):
        pass
    recs = tracing.records()
    assert [r.name for r in recs] == ["rag.answer_batch", "s1.probe",
                                      "s4.answer", "s4.prefill",
                                      "embed.encode"]
    assert [r.parent for r in recs] == [None, 0, 0, 2, None]
    assert [r.request for r in recs] == [(7, None), (7, None), (7, 1),
                                         (7, 1), (None, None)]
    assert recs[0].attrs == {"queries": 2}
    assert recs[1].attrs == {"rows": 2, "clusters": 3}
    for r in recs:
        assert r.start <= r.end
        if r.parent is not None:
            p = recs[r.parent]
            assert p.start <= r.start and r.end <= p.end
    tracing.reset()
    assert tracing.records() == []


def test_a_running_profiler_trace_holds_spans_by_name(tmp_path):
    import jax
    tracing.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracing.span("s4.prefill", step=1):
            jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events}
    assert "s4.prefill" in names            # the bare name, no attributes
    assert tracing.records() == []          # annotated, not recorded


@pytest.fixture(scope="module")
def served():
    """A reduced engine whose batch probes every cluster: some are stored,
    the others regenerate (the cache holds nothing)."""
    from repro.core import EdgeCostModel, EdgeRAGIndex
    from repro.data.embedder import ModelEmbedder
    from repro.data.synthetic import scaled_beir
    from repro.serving.engine import GeneratorModel, RAGEngine
    ds = scaled_beir("fiqa", n_records=400, n_queries=2, seed=0)
    emb = ModelEmbedder(seed=0)
    cost = EdgeCostModel()
    index = EdgeRAGIndex(emb.dim, emb, ds.get_chunks, cost, slo_s=0.05,
                         cache_bytes=0)
    index.build(ds.chunk_ids, ds.texts, nlist=12, embeddings=emb(ds.texts),
                seed=0)
    assert 0 < sum(c.stored for c in index.clusters) < index.nlist
    engine = RAGEngine(index, GeneratorModel(seed=0), cost_model=cost, k=4,
                       nprobe=index.nlist, max_new_tokens=MAX_NEW_TOKENS)
    texts = ds.query_texts[:2]
    engine.answer_batch(texts, emb(texts), ds.get_chunks)    # compiles
    tracing.reset()
    tracing.enable()
    try:
        t0 = time.perf_counter()
        out = engine.answer_batch(texts, emb(texts), ds.get_chunks)
        whole = time.perf_counter() - t0
    finally:
        tracing.disable()
    recs = tracing.records()
    tracing.reset()
    return engine, out, whole, recs


def _ancestors(recs, r):
    while r.parent is not None:
        r = recs[r.parent]
        yield r


def test_a_served_batch_records_every_span(served):
    engine, out, _, recs = served
    assert set(SERVED_SPANS) <= {r.name for r in recs}
    (root,) = [r for r in recs if r.name == "rag.answer_batch"]
    assert root.request == (engine.batches - 1, None)
    assert root.attrs == {"queries": 2}
    # the query embedding is outside the batch; the regeneration's
    # embedding nests under s2.regen
    embeds = [r for r in recs if r.name.startswith("embed.")]
    assert {r.request for r in embeds
            if not any(a.name == "s2.regen" for a in _ancestors(recs, r))
            } == {(None, None)}
    regen = [r for r in recs if r.name == "s2.regen"]
    assert regen and all(r.attrs["clusters"] > 0 and r.attrs["rows"] > 0
                         for r in regen)
    assert any(any(a.name == "s2.regen" for a in _ancestors(recs, r))
               for r in embeds)
    for qi, resp in enumerate(out):
        mine = [r for r in recs if r.request == (root.request[0], qi)]
        # the prefill gives the first token, a decode step each of the rest
        (decode,) = [r for r in mine if r.name == "s4.decode"]
        assert decode.attrs == {"steps": MAX_NEW_TOKENS - 1}
        steps = [r for r in mine if r.name == "s4.decode_step"]
        assert [r.attrs["step"] for r in steps] == list(
            range(MAX_NEW_TOKENS - 1))
        (read,) = [r for r in mine if r.name == "s4.read_tokens"]
        assert all(recs[r.parent] is decode for r in steps + [read])
        assert all(any(a is root for a in _ancestors(recs, r))
                   for r in steps)
        (tok,) = [r for r in mine if r.name == "s4.tokenize"]
        assert tok.attrs["tokens"] > 0
        assert len(resp.output_tokens) == MAX_NEW_TOKENS
    for r in recs:
        assert r.start <= r.end


def test_first_token_time_lies_between_retrieval_and_the_whole_call(served):
    _, out, whole, recs = served
    retrieval = sum(r.elapsed for r in recs
                    if r.name in ("s1.stage", "s2.stage", "s3.stage"))
    (root,) = [r for r in recs if r.name == "rag.answer_batch"]
    prefills = [r for r in recs if r.name == "s4.prefill"]
    for resp, prefill in zip(out, prefills):
        assert retrieval < resp.ttft_wall_s < whole
        # the first token reaches the host where its prefill span ends
        assert resp.ttft_wall_s == pytest.approx(prefill.end - root.start,
                                                 abs=1e-3)
    assert out[0].ttft_wall_s < out[1].ttft_wall_s


def test_what_the_benchmark_wraps_is_still_there(served):
    """bench/cell.py wraps these attributes on the instances; generate
    still dispatches through ``_prefill`` and ``_decode``."""
    engine = served[0]
    for name in ("stage_plan", "stage_fetch", "stage_score",
                 "stage_decode"):
        assert callable(getattr(engine, name))
    assert callable(engine.index.search_finish)
    assert callable(engine.index.resolver.pack_slab)
    assert callable(engine.index.embed_fn)
    gen = engine.generator
    prefill, decode, calls = gen._prefill, gen._decode, []
    gen._prefill = lambda *a: calls.append("prefill") or prefill(*a)
    gen._decode = lambda *a: calls.append("decode") or decode(*a)
    try:
        tokens = gen.generate("a short prompt", MAX_NEW_TOKENS)
    finally:
        gen._prefill, gen._decode = prefill, decode
    assert calls == ["prefill"] + ["decode"] * (MAX_NEW_TOKENS - 1)
    assert tokens == gen.generate("a short prompt", MAX_NEW_TOKENS)


def test_device_programs_have_stable_names(served):
    import jax.numpy as jnp
    engine = served[0]
    gen, emb = engine.generator, engine.index.embed_fn
    toks = jnp.zeros((1, gen.max_prompt), jnp.int32)
    caches = gen._init_cache(gen.cfg, 1, gen.max_prompt + 1)
    assert gen._prefill.lower(gen.params, {"tokens": toks}, caches
                              ).as_text().startswith(
        "module @jit_generator_prefill")
    assert gen._decode.lower(gen.params, toks[:, :1], caches, 4
                             ).as_text().startswith(
        "module @jit_generator_decode")
    row = jnp.zeros((1, emb.max_len), jnp.int32)
    assert emb._jit_encode.lower(emb.params, row, row).as_text().startswith(
        "module @jit_encoder_forward")
