"""The precision control (the reference one precision below what the
configuration states: int8 weights) must come out not correct against
the configuration's own limits, at a size a test run can hold; the
program, float32 on the CPU, meets the same limits."""
import json

import pytest

from bench import check
from conftest import CELLS, CONFIG_OF, ROOT, run_small


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_fails_the_configurations_limits(workload, seed):
    limits = json.loads((ROOT / "bench" / "configs" /
                         f"{CONFIG_OF[workload]}.json").read_text())["limits"]
    r = run_small(workload, seed=seed, control=True, layers=8, width=256,
                  vocab=2048, own_limits=True)
    assert r["correct"] is True, r["checks"]
    ok, rows = check.compare(r["control"], limits)
    assert not ok, rows
