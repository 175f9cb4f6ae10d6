"""Find a cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 2,3,4,5

Builds and warms the cell once, then serves one open-loop window per rate
and prints, for each, the answered rate, latency quantiles, and the median
latency of the window's last quarter against its first: a backlog that
grows all through the window marks a rate past the knee.  The knee goes
into the cell's traffic file as a number; benchmark runs never search.
``service_s_per_req`` (serving-loop busy seconds per request) gives the
capacity at one rate: its inverse.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402


def main(argv=None, *, chips_check=None, spec_hook=None):
    """Serve one window per rate; returns the printed rows.
    ``chips_check`` and ``spec_hook`` as for ``bench.run.run``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench import cell as cellmod, run as runmod
    from bench.traffic.generate import (arrival_times, batch_groups,
                                        make_queries)
    from repro.launch.compile_cache import configure_compile_cache

    _, entry, config, traffic = cellmod.load_spec(args.workload)
    (chips_check or runmod.require_chips)(entry["chips"])
    if spec_hook is not None:
        config, traffic = spec_hook(config, traffic)
    configure_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    with cellmod.persist_compiles(True):
        cell = cellmod.build(config, args.seed, 1)
    cellmod.instrument(cell)
    cellmod.warm_up(cell, traffic, args.seed)
    windows = []
    for i, rate in enumerate(rates):
        t = arrival_times(args.seconds, rate,
                          np.random.default_rng([args.seed, 2, i]))
        texts, _ = make_queries(config, cell.corpus.sizes, len(t),
                                args.seed, 10 + i)
        groups = batch_groups(t, int(traffic["max_batch"]),
                              float(traffic["batch_window_s"]))
        cellmod.prepare_window(cell, traffic, texts, groups)
        windows.append((t, texts, groups))
    rows = []
    for rate, (t, texts, groups) in zip(rates, windows):
        cell.clear_records()
        with cellmod.persist_compiles(False):
            served, t0, _ = cellmod.serve_window(cell, texts, t, groups)
        lat = np.array([s.done - s.due for s in served])
        q = max(1, len(lat) // 4)
        last = max(s.done for s in served) - t0
        busy = cell.spans.total("bench.batch")
        rows.append({
            "rate": rate, "n": len(lat),
            "answered_per_s": len(lat) / max(args.seconds, last),
            "busy_share": busy / last,
            "service_s_per_req": busy / len(lat),
            "p50": float(np.median(lat)),
            "p90": float(np.percentile(lat, 90)), "max": float(lat.max()),
            "first_quarter_p50": float(np.median(lat[:q])),
            "last_quarter_p50": float(np.median(lat[-q:])),
            "batches": len({s.batch for s in served}),
            "regen_per_req": sum(s.response.retrieval.n_generated
                                 for s in served) / len(served),
            "storage_per_req": sum(s.response.retrieval.n_storage_loads
                                   for s in served) / len(served)})
        print(json.dumps(rows[-1]), flush=True)
    return rows


if __name__ == "__main__":
    main()
