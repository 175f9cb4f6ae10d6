"""Run one cell of the benchmark once, on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Builds the cell named in ``BENCHMARK.json`` from its configuration and
traffic files, warms up every shape the window uses, serves open-loop
traffic for ``--seconds``, checks the answers against the plain reference
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and the
numbers compared beside their limits (``checks``).  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics: the
device metrics from a profiler trace of the window's first seconds, the
host-span metrics from the rest of the window, which the profiler does not
slow.

Needs a TPU with as many chips as the cell asks for: without one it exits
non-zero before it prints a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

TRACE_SECONDS = 8.0      # the traced part of a --trace 1 window
TRACE_DIR = ROOT / "bench" / ".trace"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also print the precision control's readings "
                         "(for setting limits; never in a benchmark run)")
    return ap.parse_args(argv)


def require_chips(n: int):
    """The device JAX finds, or exit non-zero: a run on anything but a TPU
    with ``n`` chips reports nothing."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.exit(f"bench/run.py: needs {n} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def load_reader(name: str):
    """The per-layer metric's reader, ``bench/metrics/<name>.py``."""
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Window:
    """What the per-layer readers read: the window's spans and counts, the
    served requests, and the trace when there is one.  Spans, requests and
    regenerations are those of the batches that started at or after
    ``since`` (the end of the traced part), so that the profiler's cost
    stays out of them."""

    def __init__(self, cell, served, compiles, traced_batches, trace_obj,
                 peak, chips, since):
        self.config, self.spans = cell.config, cell.spans.since(since)
        starts = [s for s, _ in cell.spans.by_name.get("bench.batch", ())]
        self.served = [s for s in served if starts[s.batch] >= since]
        self.n_requests = len(self.served)
        self.compiles = compiles
        self.probes, self.slab_work = cell.probes, cell.slab_work
        self.regen_tokens = [n for t, n in cell.regen_tokens if t >= since]
        self.traced_batches = traced_batches
        self.trace = trace_obj
        self.peak, self.chips = peak, chips
        self.query_tokens = [len(s.query.split()) + 1 for s in self.served]
        self.prompt_tokens = [
            1 + sum(len(t.split()) for t in
                    cell.corpus.get_chunks(s.response.chunk_ids))
            + len(s.query.split()) for s in self.served]

    def kernel_seconds(self, pattern: str) -> float:
        from bench import trace
        return trace.kernel_seconds(self.trace, pattern)

    def per_request_ms(self, span: str):
        """Host milliseconds per request in ``span``; None where the span
        was never entered."""
        if not self.n_requests or span not in self.spans.by_name:
            return None
        return 1e3 * self.spans.total(span) / self.n_requests


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values), q))


def run(argv=None, *, chips_check=require_chips, spec_hook=None,
        load_trace=None):
    """One run; returns the result dict it printed.  ``chips_check``,
    ``spec_hook`` (``(config, traffic) -> (config, traffic)``) and
    ``load_trace`` (a trace file -> ``bench.trace.Trace``) let a CPU test
    drive the rest of a run."""
    args = parse_args(argv)
    import jax
    from bench import cell as cellmod, check, flops, trace as trace_mod
    from bench.traffic.generate import arrival_times, batch_groups
    from repro.launch.compile_cache import configure_compile_cache

    bench, entry, config, traffic = cellmod.load_spec(args.workload)
    devs = chips_check(entry["chips"])
    if spec_hook is not None:
        config, traffic = spec_hook(config, traffic)
    configure_compile_cache()
    kind = devs[0].device_kind
    peak = flops.peaks(kind) if devs[0].platform == "tpu" else None

    rate = float(traffic["rate_per_s"])
    # one realisation of the arrival process per mix, whatever the seed:
    # the order of the gaps alone moves a queue's quantiles by tens of
    # percent, so seeded orders would measure the draw, not the system
    arrivals = arrival_times(args.seconds, rate,
                             np.random.default_rng(
                                 [int(traffic["arrival_seed"]), 2]),
                             burst=int(traffic.get("burst", 1)),
                             burst_gap_frac=float(
                                 traffic.get("burst_gap_frac", 0.1)))
    groups = batch_groups(arrivals, int(traffic["max_batch"]),
                          float(traffic["batch_window_s"]))
    with cellmod.persist_compiles(True):
        cell = cellmod.build(config, args.seed, len(arrivals))
    cellmod.instrument(cell)
    cellmod.warm_up(cell, traffic, args.seed)
    cellmod.prepare_window(cell, traffic, cell.corpus.query_texts, groups)
    cell.clear_records()

    compiles = {"n": 0, "on": False}

    def on_compile(event, duration, **_):
        if compiles["on"] and event == \
                "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_compile)

    tracing = {}

    def trace_end():
        tracing["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        tracing["batches"] = len(cell.spans.by_name.get("bench.batch", ()))
        tracing["end"] = time.perf_counter()

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    pauses, gc_start = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            pauses.append((info["generation"],
                           time.perf_counter() - gc_start[0]))
    gc.callbacks.append(on_gc)
    # set-up's objects (corpus, index, programs) live for the whole run:
    # kept out of the collector's scans, as a long-running server does
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    with cellmod.persist_compiles(False):
        compiles["on"] = True
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            tracing["ann"] = jax.profiler.TraceAnnotation("bench.window")
            tracing["ann"].__enter__()
        served, t0, late = cellmod.serve_window(
            cell, cell.corpus.query_texts, arrivals, groups,
            trace_until=TRACE_SECONDS if args.trace else None,
            on_trace_end=trace_end if args.trace else None)
        compiles["on"] = False
    gc.callbacks.remove(on_gc)
    last_done = max(s.done for s in served)
    elapsed = max(args.seconds, last_done - t0)
    stats = devs[0].memory_stats() or {}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:entry["chips"]])

    # each answer's passage scores as S3 produced them; an answer whose
    # passages are not the ones S3 chose keeps no score and counts failed
    for b, (ids, vals) in enumerate(cell.scores):
        members = [s for s in served if s.batch == b]
        for pos, s in enumerate(members[:len(ids)]):
            n = len(s.response.chunk_ids)
            if [int(i) for i in ids[pos][:n]] == s.response.chunk_ids:
                s.score = vals[pos][:n]

    for s, top in zip(served, cell.top_logits):
        s.top_logits = np.asarray(jax.numpy.concatenate(top))
    latencies = [s.done - s.due for s in served]
    n_regen = sum(s.response.retrieval.n_generated for s in served)
    tiers = {t: sum(getattr(s.response.retrieval, t) for s in served)
             for t in ("n_generated", "n_storage_loads", "n_cache_hits",
                       "n_shared_hits")}
    n_batches = len(cell.spans.by_name.get("bench.batch", ()))
    print(f"setup phases (s): {json.dumps(cell.setup)}; setup_s {setup_s}",
          flush=True)
    print(f"window: {len(served)} requests offered at {rate} req/s over "
          f"{args.seconds} s in {n_batches} batches; last answer "
          f"{last_done - t0} s after the window opened", flush=True)
    print(f"latency s: p50 {percentile(latencies, 50)} p90 "
          f"{percentile(latencies, 90)} p99 {percentile(latencies, 99)} "
          f"max {max(latencies)} (n={len(latencies)})", flush=True)
    print(f"loop lateness s: n {len(late)} median "
          f"{statistics.median(late) if late else 0} max "
          f"{max(late) if late else 0}", flush=True)
    print(f"tiers: {tiers}; regenerated clusters/request "
          f"{n_regen / len(served)}; stored clusters "
          f"{sum(c.stored for c in cell.index.clusters)} of "
          f"{cell.index.nlist}", flush=True)
    print(f"compiles in window: {compiles['n']}; device memory peak "
          f"{mem_peak} bytes (limit {stats.get('bytes_limit')})", flush=True)
    print(f"gc in window: {len(pauses)} collections, "
          f"{sum(d for _, d in pauses)} s, longest "
          f"{max(pauses, key=lambda g: g[1]) if pauses else None} "
          f"(generation, s)", flush=True)
    for s0, s1 in sorted(cell.spans.by_name.get("bench.batch", ()),
                         key=lambda iv: iv[0] - iv[1])[:3]:
        parts = {n: sum(min(e, s1) - max(b, s0) for b, e in ivs
                        if b < s1 and e > s0)
                 for n, ivs in cell.spans.by_name.items()
                 if n != "bench.batch"}
        print(f"slow batch at {s0 - t0} s: {s1 - s0} s; "
              f"{json.dumps(parts)}", flush=True)

    trace_obj, breakdown = None, None
    if args.trace:
        trace_obj = (load_trace or trace_mod.load)(
            trace_mod.find_xplane(str(TRACE_DIR)))
        breakdown = trace_mod.breakdown(trace_obj)
        busy_s = trace_mod.busy_seconds(trace_obj)
        w0, w1 = trace_obj.window
        print(f"traced {w1 - w0} s, {tracing['batches']} batches: device "
              f"busy {busy_s} s; whole-window idle "
              f"{100 * (1 - busy_s / (w1 - w0))} %", flush=True)
        print(f"breakdown: {json.dumps(breakdown)}", flush=True)

    window = Window(cell, served, compiles["n"], tracing.get("batches", 0),
                    trace_obj, peak, entry["chips"],
                    since=tracing.get("end", t0))
    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if entry["name"] not in m.get("workloads", [entry["name"]]):
                continue
            value = load_reader(m["name"])(window)
            if value is None:
                print(f"per-layer metric {m['name']}: nothing to read, "
                      f"left out", file=sys.stderr, flush=True)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"latency_p50_s": percentile(latencies, 50),
               "latency_p90_s": percentile(latencies, 90),
               "requests_per_s": len(served) / elapsed,
               "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if entry["name"] in m.get("workloads", [entry["name"]]):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # correctness: free the program's state, then the reference
    ok_rows = check.answered(served, config["k"],
                             config["generator"]["max_new_tokens"],
                             config["generator"]["vocab_size"])
    failed = ok_rows.count(False)
    whole = [s for s, ok in zip(served, ok_rows) if ok]
    probes, centroids = list(cell.probes), cell.index.centroids
    slabs, chosen = list(cell.slabs), [ids for ids, _ in cell.scores]
    cell.engine = cell.index = cell.embedder = None
    window = None
    gc.collect()
    t_ref = time.perf_counter()
    readings = {"failed": float(failed)}
    if whole:
        readings.update(check.retrieval_readings(cell, whole))
        readings["probe_gap"] = check.probe_gap(centroids, probes)
        readings["slab_gap"] = check.slab_gap(probes, slabs, chosen)
        sample = check.gen_sample(cell, whole, args.seed)
        readings.update(check.logit_readings(cell, whole, sample))
    limits = {"failed": 0.0, **config["limits"]}
    correct, rows = check.compare(readings, limits)
    for name, value in readings.items():
        if name not in limits:
            print(f"{name} (not compared): {value}", flush=True)
    print(f"reference took {time.perf_counter() - t_ref} s", flush=True)
    ctl = None
    if args.control and whole:
        ctl = check.retrieval_readings(
            cell, whole, control=check.models.control_of(config["encoder"]))
        ctl["probe_gap"] = check.probe_gap_control(centroids, probes)
        ctl["slab_gap"] = check.slab_gap_control(probes, slabs, config["k"])
        ctl.update(check.logit_readings(
            cell, whole, sample,
            control=check.models.control_of(config["generator"])))
        print(f"control readings: {json.dumps(ctl)}", flush=True)
    for name, value, limit in rows:
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    result = {
        "correct": bool(correct and failed == 0),
        "attempted": len(served), "failed": failed,
        "metrics": metrics,
        "device": {"platform": devs[0].platform, "kind": kind,
                   "count": len(devs), "memory_peak_bytes": int(mem_peak)},
    }
    if args.trace:
        result["device"]["busy_s"] = busy_s
        result["device"]["window_s"] = w1 - w0
        result["breakdown"] = breakdown
    if ctl is not None:
        result["control"] = ctl
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in rows}
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    run()
