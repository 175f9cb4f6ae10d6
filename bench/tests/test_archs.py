"""The architecture lookup: every configuration's models resolve and match
the program; an unknown name fails; a second architecture enters as one
new module and the harness builds, serves, checks and counts through it."""
import collections
import json
import sys
import types

import pytest

from bench import cell as cellmod, models, run as runmod
from conftest import ROOT, run_small

CONFIGS = sorted(f.stem for f in (ROOT / "bench" / "configs").glob("*.json"))
# published parameter counts of the models the configurations name
PUBLISHED = {"gte-base-en-v1.5": 136.8e6, "stablelm-2-1_6b": 1.644e9}
INTERFACE = ("program_config", "init_weights", "param_count", "logits",
             "encode", "generator_flops", "encoder_flops", "small")


def model_of(name, model):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())[model]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("model", ["encoder", "generator"])
def test_param_count_matches_the_program(name, model):
    m = model_of(name, model)
    arch = models.arch(m)
    assert arch.param_count(m) == arch.program_config(m).param_count()
    if m["name"] in PUBLISHED:
        assert arch.param_count(m) == pytest.approx(PUBLISHED[m["name"]],
                                                    rel=0.01)


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("model", ["encoder", "generator"])
def test_init_weights_match_the_program_layout(name, model):
    import jax
    from repro.models import init_params
    m = model_of(name, model)
    arch = models.arch(m)
    m = arch.small(m, 2, 128, 300)
    ours = arch.init_weights(m, 3)
    theirs = jax.eval_shape(lambda: init_params(arch.program_config(m),
                                                jax.random.PRNGKey(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape


def test_a_model_without_arch_is_dense():
    from bench.archs import dense
    assert models.arch({"name": "m"}) is dense


@pytest.mark.parametrize("name", ["no_such_arch", "../dense"])
def test_unknown_arch_names_the_directory(name):
    with pytest.raises(ValueError, match="bench/archs/") as e:
        models.arch({"name": "m", "arch": name})
    assert "'dense'" in str(e.value)


def counted_copy_of_dense(monkeypatch):
    """A module ``bench.archs.counted``: dense's interface, each function
    counting its calls, put where the lookup imports from."""
    from bench.archs import dense
    calls = collections.Counter()
    mod = types.ModuleType("bench.archs.counted")

    def counting(name):
        fn = getattr(dense, name)

        def counted(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return counted
    for name in INTERFACE:
        setattr(mod, name, counting(name))
    monkeypatch.setitem(sys.modules, "bench.archs.counted", mod)
    return calls


def test_a_new_architecture_enters_as_a_module(monkeypatch):
    calls = counted_copy_of_dense(monkeypatch)
    load_spec, seen = cellmod.load_spec, {}

    def spec_naming_counted(workload):
        bench, entry, config, traffic = load_spec(workload)
        config = dict(config, **{k: dict(config[k], arch="counted")
                                 for k in ("encoder", "generator")})
        return bench, entry, config, traffic
    build = cellmod.build

    def build_seen(config, *a, **kw):
        seen["config"] = config
        return build(config, *a, **kw)
    monkeypatch.setattr(cellmod, "load_spec", spec_naming_counted)
    monkeypatch.setattr(cellmod, "build", build_seen)
    r = run_small("fiqa-steady", seed=41)
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    # cut, built, and checked through the new module
    assert calls["small"] == 2
    assert calls["init_weights"] == 2 and calls["program_config"] == 2
    assert calls["encode"] >= 2 and calls["logits"] >= 1
    # mfu counts through it too (the CPU has no peak: a made-up one)
    spans = cellmod.Spans()
    spans.by_name["bench.batch"] = [(0.0, 1.0)]
    w = types.SimpleNamespace(
        config=seen["config"], peak={"bf16_flops_per_s": 1e12}, chips=1,
        query_tokens=[9], regen_tokens=[33], prompt_tokens=[120],
        spans=spans)
    assert runmod.load_reader("mfu")(w) > 0
    assert calls["encoder_flops"] == 1 and calls["generator_flops"] == 1
