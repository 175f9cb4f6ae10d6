"""The serving launcher and its compile-cache helper, rehearsed on the CPU
with the reduced model configs (``chip_smoke.py`` runs the same
``repro.launch.serve`` path at full width on a TPU)."""
import os

import jax
import numpy as np
import pytest

from repro.launch import compile_cache, serve


@pytest.fixture
def cache_config(monkeypatch):
    """Leave JAX's persistent-cache settings as the test found them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_compile_cache_env_wins(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # nothing set in code


def test_compile_cache_default_is_fixed_and_ignored(cache_config):
    path = compile_cache.configure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_embedder_runs_a_large_call_as_capped_buckets():
    """A corpus-sized call never compiles a corpus-sized program: it runs as
    power-of-two buckets of at most MAX_BATCH rows, and each row's
    embedding does not depend on how the call was cut."""
    from repro.data.embedder import ModelEmbedder
    emb = ModelEmbedder(seed=0)
    texts = [f"passage {i} " + "word " * (i % 7) for i in range(300)]
    rows = []
    encode = emb._jit_encode
    emb._jit_encode = lambda p, t, m: rows.append(t.shape[0]) or encode(p, t, m)
    out = emb(texts)
    assert out.shape == (300, emb.dim)
    assert rows == [ModelEmbedder.MAX_BATCH, 64]
    np.testing.assert_allclose(out[256:259], emb(texts[256:259]),
                               rtol=1e-5, atol=1e-5)


def test_serve_reduced_rehearsal(cache_config):
    args = serve.parse_args(["--reduced", "--records", "600", "--queries",
                             "5", "--batch", "2", "--max-new-tokens", "2"])
    run = serve.serve(args)
    assert len(run.responses) == 5 and len(run.batch_wall_s) == 3
    assert [len(e) for e in run.query_embs] == [2, 2, 1]
    ids = set(run.ds.chunk_ids.tolist())
    vocab = run.engine.generator.cfg.vocab_size
    for r in run.responses:
        assert len(r.chunk_ids) == args.k and set(r.chunk_ids) <= ids
        assert len(r.output_tokens) == 2
        assert all(0 <= t < vocab for t in r.output_tokens)
    # the index's regeneration encoder is the one that embedded the corpus
    assert run.index.embed_fn is run.embedder
    regen = run.embedder(run.ds.texts[:3])
    np.testing.assert_allclose(regen, run.corpus_emb[:3], rtol=1e-5,
                               atol=1e-5)
    assert sum(serve.counts(run)[t] for t in
               ("n_generated", "n_storage_loads", "n_cache_hits")) > 0
