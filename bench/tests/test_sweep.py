"""The knee sweep serves one window per rate through the same cell code."""
import jax

from conftest import CELLS, small


def test_sweep_serves_each_rate():
    from bench import sweep
    rows = sweep.main(["--workload", CELLS[-1], "--seed", "5",
                       "--seconds", "2", "--rates", "1,3"],
                      chips_check=lambda n: jax.devices(),
                      spec_hook=small)
    assert [r["rate"] for r in rows] == [1.0, 3.0]
    assert [r["n"] for r in rows] == [2, 6]
    for r in rows:
        assert 0 < r["busy_share"] <= 1 and r["service_s_per_req"] > 0
