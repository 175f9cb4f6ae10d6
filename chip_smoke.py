"""Bring-up check: the EdgeRAG served path on one TPU, at full model width.

    python chip_smoke.py              # one chip: build, serve, check
    python chip_smoke.py --chips 4    # the four-chip slab route only

One chip: ``repro.launch.serve`` builds the 25,000-passage FiQA-shaped
corpus, embeds it with gte-base-en-v1.5 (768-d, 12 layers), indexes it
with EdgeRAG and answers 32 requests in batches of 8 with
sheared-llama-2.7b generating; both models hold random weights from a
seed.  Then it checks, and exits non-zero if any check fails:

* the compiled Pallas ``ivf_topk`` and ``slab_topk`` agree with their
  jnp references on a served batch (scores within 1e-5; ids equal except
  where the two picks tie within 1e-5 in float64);
* EdgeRAG's recall@10 against exact ``FlatIndex`` search is within 0.02
  of an ``IVFIndex`` at the same nlist and nprobe;
* requests regenerated clusters on the chip and loaded stored ones;
* generated tokens are valid ids, and greedy decoding of a served prompt
  gives the served tokens twice more;
* no maintenance op failed or was quarantined.

``--chips 4``: builds the same index without the generator and scores one
served batch's packed slab through ``sharded_slab_topk`` on a 4-device
``("data",)`` mesh and through ``slab_topk`` on one chip; ids must agree
(near-ties excepted, as above), scores must be close, and the slab and
membership shards must sit on four distinct devices.  Then the same batch
runs end to end through ``search_batch(mesh=...)``.

The last line of standard output is one JSON object naming the device.
The script runs in one process and needs a TPU: without one it exits
non-zero before it prints any result.  The per-request wall times it
prints come from a bring-up run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.costs import LatencyBreakdown  # noqa: E402
from repro.core.edgerag import slab_score_topk  # noqa: E402
from repro.core.flat_index import FlatIndex  # noqa: E402
from repro.core.ivf_index import IVFIndex  # noqa: E402
from repro.core.sharded_retrieval import sharded_slab_topk  # noqa: E402
from repro.kernels.ivf_topk.ops import on_tpu, topk_ip  # noqa: E402
from repro.kernels.slab_topk.ops import NOT_PROBED, slab_topk  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

TOL = 1e-5              # kernel vs reference: scores, and near-tie width
RECALL_GAP = 0.02       # EdgeRAG vs IVF recall@10 (paper: "similar quality")
SERVE_ARGS = ["--queries", "32", "--batch", "8"]

FAILURES: list = []


def check(ok: bool, what: str):
    print(f"[{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        FAILURES.append(what)


def compile_seconds() -> dict:
    """Sum the backend compile time of every program JAX compiles from now
    on (persistent-cache hits compile nothing and add nothing)."""
    tally = {"seconds": 0.0, "programs": 0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tally["seconds"] += duration
            tally["programs"] += 1
    jax.monitoring.register_event_duration_secs_listener(listen)
    return tally


def agree(vals_a, ids_a, vals_b, ids_b, exact, valid) -> int:
    """Compare two (Q, k) top-k results on their ``valid`` lanes: scores
    within TOL, ids equal except at near-ties.  ``exact(q, ids)`` gives
    float64 scores.  Returns the number of near-tie swaps; raises nothing,
    the caller checks."""
    va, vb = np.asarray(vals_a), np.asarray(vals_b)
    ia, ib = np.asarray(ids_a), np.asarray(ids_b)
    close = np.allclose(va[valid], vb[valid], rtol=TOL, atol=TOL)
    qi, lane = np.nonzero((ia != ib) & valid)
    gap = np.abs(exact(qi, ia[qi, lane]) - exact(qi, ib[qi, lane]))
    ties = bool((gap <= TOL).all())
    return len(qi) if close and ties else -1


def batch_slab(index, queries, nprobe):
    """Plan, resolve and pack one batch's slab exactly as ``search_batch``
    does: returns (plan, slab, virts, n_valid_seg)."""
    plan = index.plan_batch(queries, nprobe)
    lats = [LatencyBreakdown() for _ in range(len(queries))]
    payloads = index.resolver.execute(plan, lats, [False] * len(queries),
                                      raw=True)
    slab = index.resolver.pack_slab(plan, payloads, lats)
    virts, _, n_valid_seg = slab.query_layout(plan.probed_per_q)
    return plan, slab, virts, n_valid_seg


def seg_exact(seg, q64):
    emb = seg.emb.astype(np.float64)
    if seg.scales is not None:
        emb = emb * seg.scales.astype(np.float64)
    return lambda qi, rows: np.einsum("nd,nd->n", q64[qi], emb[rows])


def kernel_parity(run, queries, k, nprobe):
    """Compiled Pallas kernels vs their jnp references on one served batch."""
    q64 = queries.astype(np.float64)
    cents = run.index.centroids
    c64 = cents.astype(np.float64)
    pv, pi = topk_ip(cents, queries, nprobe, impl="pallas")
    rv, ri = topk_ip(cents, queries, nprobe, impl="ref")
    swaps = agree(pv, pi, rv, ri,
                  lambda qi, ids: np.einsum("nd,nd->n", q64[qi], c64[ids]),
                  np.ones(np.shape(pi), bool))
    check(swaps >= 0, f"ivf_topk Pallas == ref on the centroid probe "
          f"(Q={len(queries)}, N={len(cents)}, k={nprobe}; "
          f"near-tie swaps {swaps})")
    _, slab, virts, n_valid_seg = batch_slab(run.index, queries, nprobe)
    lane = np.arange(k)[None, :]
    for seg in slab.segments:
        pv, pr = slab_topk(seg.emb, queries, virts[seg.kind], k,
                           scales=seg.scales, impl="pallas")
        rv, rr = slab_topk(seg.emb, queries, virts[seg.kind], k,
                           scales=seg.scales, impl="ref")
        valid = lane < n_valid_seg[seg.kind][:, None]
        swaps = agree(pv, pr, rv, rr, seg_exact(seg, q64), valid)
        check(swaps >= 0, f"slab_topk Pallas == ref on the {seg.kind} slab "
              f"(Q={len(queries)}, N={seg.rows}, k={k}; "
              f"near-tie swaps {swaps})")


def recall(found, truth, k) -> float:
    return float(np.mean([len(set(f[:k]) & set(t[:k])) / k
                          for f, t in zip(found, truth)]))


def recall_vs_ivf(run, args):
    queries = np.concatenate(run.query_embs)
    flat = FlatIndex(run.embedder.dim)
    flat.add(run.corpus_emb, run.ds.chunk_ids)
    truth, _, _ = flat.search(queries, args.k)
    ivf = IVFIndex(run.embedder.dim)
    ivf.build(run.corpus_emb, run.ds.chunk_ids, nlist=run.index.nlist,
              seed=args.seed)
    ivf_ids = [ivf.search(q, args.k, args.nprobe)[0][0] for q in queries]
    r_edge = recall([r.chunk_ids for r in run.responses], truth, args.k)
    r_ivf = recall(ivf_ids, truth, args.k)
    print(f"recall@{args.k} vs exact FlatIndex over {len(queries)} queries: "
          f"EdgeRAG {r_edge:.4f}, IVFIndex {r_ivf:.4f} "
          f"(nlist {run.index.nlist}, nprobe {args.nprobe})")
    check(abs(r_edge - r_ivf) <= RECALL_GAP,
          f"EdgeRAG recall within {RECALL_GAP} of IVFIndex")


def generation_checks(run, args):
    gen = run.engine.generator
    vocab = gen.cfg.vocab_size
    toks = [r.output_tokens for r in run.responses]
    check(all(len(t) == args.max_new_tokens and all(0 <= x < vocab
                                                    for x in t)
              for t in toks),
          f"every request generated {args.max_new_tokens} valid token ids")
    first = run.responses[0]
    prompt = " ".join(first.context + [first.query])
    again = [gen.generate(prompt, args.max_new_tokens) for _ in range(2)]
    check(again[0] == again[1] == first.output_tokens,
          "greedy decoding of a served prompt repeats the served tokens")


def one_chip():
    compiles = compile_seconds()
    args = serve.parse_args(SERVE_ARGS)
    t0 = time.perf_counter()
    run = serve.serve(args)
    served_s = time.perf_counter() - t0
    print(f"built and served in {served_s:.3f} s wall; set-up phases "
          f"{run.setup_s}")
    print(f"compile: {compiles['seconds']:.3f} s in {compiles['programs']} "
          f"programs so far")
    print("per-request wall seconds (bring-up run, not a benchmark; "
          "decode amortised over each batch):")
    for bi, w in enumerate(run.batch_wall_s):
        rs = run.responses[bi * args.batch:(bi + 1) * args.batch]
        print(f"  batch {bi} ({len(rs)} requests, {w:.4f} s, compiles "
              f"included): batch start to first token "
              f"{[round(r.ttft_wall_s, 4) for r in rs]}, decode "
              f"{[round(r.decode_wall_s, 4) for r in rs]}")
    tiers = serve.counts(run)
    print(f"tiers: {tiers}; cache hit rate {run.index.cache.hit_rate:.4f}; "
          f"regeneration encoder calls {run.embedder.calls}")
    check(on_tpu(), "top-k kernels dispatch to compiled Pallas (no "
          "interpret mode, no reference route)")
    check(len(run.responses) >= 16, f"{len(run.responses)} requests served")
    check(max(len(e) for e in run.query_embs) > 1,
          "a batch with more than one query went through one slab launch")
    check(tiers["n_generated"] > 0, "clusters regenerated on the chip")
    check(tiers["n_storage_loads"] > 0, "stored clusters loaded")
    sched = run.index.maintenance
    check(sched.n_failures == 0 and not sched.quarantined,
          f"maintenance: {sched.stats()}")
    kernel_parity(run, run.query_embs[0], args.k, args.nprobe)
    recall_vs_ivf(run, args)
    generation_checks(run, args)
    print(f"compile: {compiles['seconds']:.3f} s in {compiles['programs']} "
          f"programs in all")


def place_rows(mesh, seg, virt):
    """Put one slab segment's rows, membership columns and scales on the
    mesh, padded to a multiple of its size as ``sharded_slab_topk`` pads
    (zero rows that no query probes)."""
    pad = (-seg.rows) % mesh.size

    def put(a, widths, spec, fill=0):
        return jax.device_put(np.pad(a, widths, constant_values=fill),
                              NamedSharding(mesh, spec))
    emb = put(seg.emb, ((0, pad), (0, 0)), P("data", None))
    virt = put(virt, ((0, 0), (0, pad)), P(None, "data"), NOT_PROBED)
    scales = (None if seg.scales is None else
              put(seg.scales, ((0, pad), (0, 0)), P("data", None)))
    return emb, virt, scales


def four_chips():
    devices = jax.devices()
    if len(devices) < 4:
        sys.exit(f"--chips 4 needs four devices, JAX sees {len(devices)}")
    mesh = Mesh(np.array(devices[:4]), ("data",))
    args = serve.parse_args(SERVE_ARGS + ["--no-generator"])
    run = serve.build(args)
    texts = run.ds.query_texts[:args.batch]
    queries = run.embedder(texts)
    k, nprobe = args.k, args.nprobe
    q64 = queries.astype(np.float64)
    plan, slab, virts, n_valid_seg = batch_slab(run.index, queries, nprobe)
    lane = np.arange(k)[None, :]
    for seg in slab.segments:
        emb, virt, scales = place_rows(mesh, seg, virts[seg.kind])
        placed = {name: sorted(s.device.id for s in a.addressable_shards)
                  for name, a in (("slab", emb), ("virt", virt))}
        print(f"{seg.kind} slab {emb.shape}, virt {virt.shape} (padded from "
              f"{seg.rows} rows): shards on devices {placed}")
        check(all(len(set(ids)) == 4 for ids in placed.values()),
              "slab and virt shards sit on four distinct devices")
        sv, sr = sharded_slab_topk(emb, queries, virt, k, mesh,
                                   scales=scales)
        ov, orow = slab_topk(seg.emb, queries, virts[seg.kind], k,
                             scales=seg.scales)
        valid = lane < n_valid_seg[seg.kind][:, None]
        swaps = agree(sv, sr, ov, orow, seg_exact(seg, q64), valid)
        check(swaps >= 0, f"sharded_slab_topk on 4 chips == slab_topk on "
              f"one chip ({seg.kind}, Q={len(queries)}, N={seg.rows}; "
              f"near-tie swaps {swaps})")
    one_ids, one_vals, _ = slab_score_topk(slab, queries, k,
                                           plan.probed_per_q)
    mesh_ids, mesh_vals, lats = run.index.search_batch(queries, k, nprobe,
                                                       mesh=mesh)
    emb64 = run.corpus_emb.astype(np.float64)
    row_of = {int(c): i for i, c in enumerate(run.ds.chunk_ids)}

    def exact(qi, ids):
        rows = np.array([row_of[int(c)] for c in ids], np.int64)
        return np.einsum("nd,nd->n", q64[qi], emb64[rows])
    swaps = agree(mesh_vals, mesh_ids, one_vals, one_ids, exact,
                  one_ids >= 0)
    check(swaps >= 0 and np.array_equal(mesh_ids >= 0, one_ids >= 0),
          f"search_batch(mesh=4 chips) == the one-chip slab scoring of the "
          f"same batch (near-tie swaps {swaps}; clusters regenerated "
          f"{sum(l.n_generated for l in lats)})")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")
    print(f"device: {dev.platform} {dev.device_kind} x {jax.device_count()}",
          flush=True)
    print(f"compile cache: {configure_compile_cache()}")
    one_chip() if opts.chips == 1 else four_chips()
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    if FAILURES:
        sys.exit(f"chip_smoke: {len(FAILURES)} check(s) failed: {FAILURES}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
