"""CPU tests of the benchmark (``python -m pytest bench/tests``).

They run the harness on JAX's CPU backend at reduced model sizes; nothing
here measures speed."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

import json  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
CONFIG_OF = {w["name"]: w["config"] for w in BENCHMARK["workloads"]}

# limits a sound CPU run meets by orders of magnitude (float32 against a
# float32 reference); the faults read far above them
CPU_LIMITS = {"query_embed_gap": 1e-4, "probe_gap": 1e-6, "slab_gap": 1e-6,
              "logit_error": 1e-3}


def small(cfg: dict, traffic: dict, rate: float = 2.0, layers: int = 2,
          width: int = 128, vocab: int = 512, own_limits: bool = False):
    """A configuration cut to CPU size: same shape of corpus and traffic,
    models cut by their architecture's ``small`` to ``layers`` deep and
    ``width`` wide; the mix offered at ``rate``; the limits a sound CPU
    run meets, or with ``own_limits`` the configuration's own."""
    from bench import models
    cfg = dict(cfg, passages=1500, topics=77, nlist=46)
    enc, gen = cfg["encoder"], cfg["generator"]
    cfg["encoder"] = models.arch(enc).small(enc, layers, width, vocab)
    cfg["generator"] = dict(models.arch(gen).small(gen, layers, width, vocab),
                            max_prompt=256, max_new_tokens=4)
    if not own_limits:
        cfg["limits"] = dict(CPU_LIMITS)
    return cfg, dict(traffic, rate_per_s=rate)


def run_small(workload, seed=11, seconds=3.0, rate=2.0, trace=0, hook=None,
              control=False, load_trace=None, **size):
    """One run of ``workload`` on the CPU at reduced size (``size``: the
    keywords of ``small``); ``hook(cell)`` may break the timed path after
    the engine is built."""
    import jax
    from bench import cell as cellmod, run as runmod
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)] + (["--control"] * control)
    build = cellmod.build

    def broken_build(*a, **kw):
        cell = build(*a, **kw)
        hook(cell)
        return cell
    if hook is not None:
        cellmod.build = broken_build
    try:
        return runmod.run(argv, chips_check=lambda n: jax.devices(),
                          spec_hook=lambda c, t: small(c, t, rate, **size),
                          load_trace=load_trace)
    finally:
        cellmod.build = build


@pytest.fixture(scope="session")
def bench_json():
    return BENCHMARK
