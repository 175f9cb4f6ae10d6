"""The generator of corpus, queries and arrivals, and finding mixes by
name."""
import json

import numpy as np
import pytest
from scipy.special import zeta

from bench import cell as cellmod
from bench.traffic import generate as gen
from conftest import ROOT

CFG = json.loads((ROOT / "bench" / "configs" /
                  "fiqa-gte-stablelm2.json").read_text())
SMALL = dict(CFG, passages=3000, topics=109)


def test_same_seed_same_inputs():
    a, b = gen.make_corpus(SMALL, 50, 2**33 + 7), gen.make_corpus(SMALL, 50,
                                                                  2**33 + 7)
    assert a.texts == b.texts and a.query_texts == b.query_texts
    assert (a.topic_of == b.topic_of).all()
    t1 = gen.arrival_times(20.0, 4.0, np.random.default_rng([2**40, 2]))
    t2 = gen.arrival_times(20.0, 4.0, np.random.default_rng([2**40, 2]))
    assert (t1 == t2).all()
    c = gen.make_corpus(SMALL, 50, 2**33 + 8)
    assert c.texts != a.texts


def test_seeds_share_the_work():
    """Two seeds: the same multiset of topic sizes, passage lengths and
    gaps, in another order."""
    a, b = gen.make_corpus(SMALL, 64, 1), gen.make_corpus(SMALL, 64, 2)
    assert sorted(a.sizes) == sorted(b.sizes)
    words = sorted(len(t.split()) for t in a.texts)
    assert words == sorted(len(t.split()) for t in b.texts)
    ga = np.diff(gen.arrival_times(30.0, 3.0, np.random.default_rng(1)),
                 prepend=0.0)
    gb = np.diff(gen.arrival_times(30.0, 3.0, np.random.default_rng(2)),
                 prepend=0.0)
    assert np.allclose(sorted(ga), sorted(gb))


@pytest.mark.parametrize("rate", [0.5, 3.0, 4.2])
def test_poisson_rate_and_shape(rate):
    seconds = 200.0
    t = gen.arrival_times(seconds, rate, np.random.default_rng(5))
    assert len(t) == round(rate * seconds)
    assert 0 <= t[0] and t[-1] < seconds and (np.diff(t) > 0).all()
    gaps = np.diff(t) * rate
    # exponential gaps: mean 1, coefficient of variation 1, median ln 2
    assert gaps.mean() == pytest.approx(1.0, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.1)
    assert np.median(gaps) == pytest.approx(np.log(2), rel=0.05)


def test_bursty_keeps_the_mean_rate():
    t = gen.arrival_times(100.0, 4.0, np.random.default_rng(5), burst=4)
    assert len(t) == 400 and t[-1] < 100.0
    gaps = np.diff(t)
    assert np.median(gaps) < 0.5 * gaps.mean()       # back-to-back bursts


def test_batch_groups_follow_the_arrivals_alone():
    t = np.array([0.0, 0.05, 0.3, 0.31, 0.32, 0.33, 2.0])
    # window 0: every request alone, ready at its own arrival
    assert gen.batch_groups(t, 8, 0.0) == [(i, i + 1, t[i])
                                           for i in range(len(t))]
    assert gen.batch_groups(t, 1, 0.5) == gen.batch_groups(t, 1, 0.0)
    # a window takes what arrives within it; a full batch is ready at
    # its last arrival, one that is not full when its window closes
    assert gen.batch_groups(t, 3, 0.1) == [(0, 2, 0.1), (2, 5, 0.32),
                                           (5, 6, 0.33 + 0.1),
                                           (6, 7, 2.0 + 0.1)]
    groups = gen.batch_groups(gen.arrival_times(
        60.0, 3.0, np.random.default_rng(4)), 8, 0.25)
    assert [i for i, _, _ in groups[1:]] == [j for _, j, _ in groups[:-1]]
    assert all(0 < j - i <= 8 for i, j, _ in groups)


@pytest.mark.parametrize("a", [1.25, 2.2])
def test_zipf_statistics(a):
    n, topics = 4000, 300
    ranks = gen.zipf_ranks(n, a, topics, np.random.default_rng(3))
    for r in range(3):
        expect = (r + 1) ** -a / zeta(a)
        assert np.mean(ranks == r) == pytest.approx(expect, abs=2.0 / n)
    assert ranks.max() <= topics - 1


def test_lognormal_sizes_sum_and_tail():
    sizes = gen.lognormal_sizes(25000, 316, 1.0, np.random.default_rng(0))
    assert sizes.sum() == 25000 and sizes.min() >= 1
    # sigma 1: the largest topic is several times the median
    assert sizes.max() > 5 * np.median(sizes)


def test_passage_and_query_words():
    c = gen.make_corpus(SMALL, 400, 9)
    words = np.array([len(t.split()) - 1 for t in c.texts])
    assert words.mean() == pytest.approx(CFG["doc_words"], rel=0.01)
    assert words.std() / words.mean() == pytest.approx(0.3, rel=0.05)
    qw = np.array([len(t.split()) - 1 for t in c.query_texts])
    assert qw.min() >= 7 and qw.max() <= 27
    # each passage speaks its topic's vocabulary
    vocab = set(gen.topic_words(int(c.topic_of[5])))
    assert set(c.texts[5].split()[1:]) <= vocab


def test_a_new_mix_is_found_by_name(tmp_path):
    """A mix added as a data file, with a cell naming it, runs through the
    same harness code: nothing existing is edited."""
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    mix = {"why": "test", "rate_per_s": 1.5, "burst": 3,
           "warmup_requests": 8, "arrival_seed": 4}
    (tmp_path / "bench" / "traffic" / "my-new-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "configs" / "fiqa-gte-stablelm2.json").write_text(
        json.dumps(CFG))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "fiqa-new", "config":
                               "fiqa-gte-stablelm2",
                               "traffic": "my-new-mix", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    _, entry, config, traffic = cellmod.load_spec("fiqa-new", tmp_path)
    assert traffic == mix and config == CFG and entry["chips"] == 1
