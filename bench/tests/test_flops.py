"""Operations, bytes and model FLOPs from shapes; the peak table."""
import json

import pytest

from bench import flops, models
from conftest import ROOT


def test_ivf_topk_counts_by_hand():
    # 8 queries against 781 centroids of 768 floats
    f, b = flops.ivf_topk(8, 781, 768)
    assert f == 2 * 8 * 781 * 768 == 9596928
    assert b == 4 * 781 * 768 + 4 * 8 * 768 == 2423808


def test_slab_topk_counts_by_hand():
    # two queries probe 100 and 60 rows of a 130-row slab, k = 10
    f, b = flops.slab_topk(768, 10, [100, 60], 130)
    assert f == 2 * 768 * 160
    assert b == 4 * 130 * 768 + 4 * 2 * 768 + 8 * 2 * 10


def test_roofline_share_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_share(1000, 5, 20.0, peak) == pytest.approx(50.0)
    assert flops.roofline_share(10, 100, 20.0, peak) == pytest.approx(50.0)


@pytest.mark.parametrize("name", sorted(
    f.stem for f in (ROOT / "bench" / "configs").glob("*.json")))
@pytest.mark.parametrize("model", ["encoder", "generator"])
def test_param_count_matches_the_program(name, model):
    m = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    m = m[model]
    assert models.param_count(m) == models.model_config(m).param_count()
    # the generator is stablelm-2-1.6b's 1.64 B; gte-base about 137 M
    expect = {"encoder": 136.8e6, "generator": 1.644e9}[model]
    assert models.param_count(m) == pytest.approx(expect, rel=0.01)


def test_init_weights_match_the_program_layout():
    import jax
    from repro.models import init_params
    m = dict(json.loads((ROOT / "bench" / "configs" /
                         "fiqa-gte-stablelm2.json").read_text())["generator"],
             num_layers=2, hidden_size=64, num_heads=2, num_kv_heads=2,
             head_dim=32, intermediate_size=96, vocab_size=300)
    ours = models.init_weights(m, 3)
    theirs = jax.eval_shape(lambda: init_params(models.model_config(m),
                                                jax.random.PRNGKey(0)))
    assert (jax.tree.structure(ours) == jax.tree.structure(theirs))
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape


def test_model_flops_by_hand():
    m = {"num_layers": 2, "hidden_size": 4, "num_heads": 2, "head_dim": 2,
         "num_kv_heads": 2, "intermediate_size": 8, "vocab_size": 10}
    n = flops.non_embedding_params(m)
    assert n == 2 * (4 * 12 + 16 + 8 + 96) + 4
    # encoder: one row of 3 tokens
    assert flops.encoder_flops(m, [3]) == 2 * n * 3 + 4 * 2 * 4 * 9
    # generator: 3 prompt tokens, 2 new: 4 tokens fed, head twice
    assert flops.generator_flops(m, 3, 2) == (2 * n * 4 + 4 * 2 * 4 * 10
                                              + 2 * 4 * 10 * 2)


def test_peaks_known_and_unknown_kind(tmp_path):
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v99")
