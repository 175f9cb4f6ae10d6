"""A whole run of each cell on the CPU at reduced size, and the command's
refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import cell as cellmod, trace
from conftest import CELLS, ROOT, run_small


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(workload, bench_json):
    r = run_small(workload, seed=2**31 + 5)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == 6
    assert set(r["metrics"]) == {m["name"] for m in bench_json["end_to_end"]}
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


def cpu_trace(path):
    """The CPU has no device plane: the host spans of the CPU trace, with
    each generation span standing in as a device operation."""
    t = trace.read_planes(path)
    t.ops = [[(s, e, "fusion.1", "jit_step(1)")
              for s, e in t.spans("s4.generate")]]
    return t


def test_traced_run_reports_every_metric(monkeypatch, bench_json):
    from bench import run as runmod
    monkeypatch.setattr(runmod, "TRACE_SECONDS", 1.0)
    r = run_small(CELLS[-1], seed=77, seconds=4.0, trace=1,
                  load_trace=cpu_trace)
    assert r["correct"] is True
    # the CPU has no peak table row: the shares of a peak are left out,
    # never read as 0; everything else is read
    peaks = {"ivf_topk_roofline", "slab_topk_roofline", "mfu"}
    assert set(r["metrics"]) == {m["name"] for m in bench_json["per_layer"]
                                 } - peaks
    assert 0 < r["metrics"]["idle_share"]["value"] < 100
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] > 0
    assert r["breakdown"]["device_ops"][0][0] == "jit_step"
    # every slab shape of the window was compiled in set-up
    assert r["metrics"]["compiles_in_window"]["value"] == 0


def test_a_trace_without_device_operations_fails_the_run(monkeypatch):
    from bench import run as runmod
    monkeypatch.setattr(runmod, "TRACE_SECONDS", 1.0)
    with pytest.raises(ValueError, match="no TPU plane"):
        run_small("fiqa-steady", seed=78, seconds=2.0, trace=1)


def test_wrapping_a_missing_method_fails():
    class Engine:
        def stage_plan(self):
            return 1
    with pytest.raises(AttributeError):
        cellmod.wrap(Engine(), "stage_gone", cellmod.Spans(), "s1.plan")


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fiqa-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    lines = out.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_command_refuses_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "needs 1 TPU chip" in p.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no program to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout)


def test_fill_cache_holds_every_probed_cluster():
    """The mix's fill_cache: every cluster the window probes that the index
    does not store is cached before the window, and the Alg. 3 threshold
    controller has seen no request."""
    import numpy as np
    from bench.traffic.generate import arrival_times, batch_groups
    from conftest import small
    _, _, config, traffic = cellmod.load_spec("fiqa-steady")
    config, traffic = small(config, traffic)
    cell = cellmod.build(config, 2**32 + 9, 24)
    groups = batch_groups(arrival_times(12.0, 2.0, np.random.default_rng(0)),
                          1, 0.0)
    probes = cellmod.window_probes(cell, cell.corpus.query_texts, groups)
    wanted = {c for batch in probes for p in batch for c in p
              if not cell.index.clusters[c].stored}
    assert any(c not in cell.index.cache for c in wanted)
    cellmod.fill_cache(cell, probes)
    assert all(c in cell.index.cache for c in wanted)
    assert cell.index.threshold.threshold == 0.0
    assert cell.setup["filled_clusters"] == len(wanted)
