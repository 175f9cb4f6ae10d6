"""Pod-sharded retrieval (beyond-paper, DESIGN.md §2): the EdgeRAG
second-level scan distributed over the data axis with an all-gather-of-
candidates merge.  The mesh spans every device JAX sees; without an
accelerator, 8 forced host devices stand in for the pod's data axis.

    PYTHONPATH=src python examples/pod_retrieval.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np

from repro.core.sharded_retrieval import ShardedFlatSearch
from repro.data import generate_dataset
from repro.kernels.ivf_topk.ops import topk_ip


def main():
    ds = generate_dataset(n_records=20_000, dim=128, n_topics=128,
                          n_queries=16, seed=0)
    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev, 1), ("data", "model"))
    print(f"devices: {n_dev}; corpus: {ds.n} x 128")

    search = ShardedFlatSearch(ds.embeddings, mesh)
    # warm
    search.search(ds.query_embs[:1], 10)
    t0 = time.perf_counter()
    vals, idx = search.search(ds.query_embs, 10)
    t_sharded = time.perf_counter() - t0

    t0 = time.perf_counter()
    rv, ri = topk_ip(ds.embeddings, ds.query_embs, 10)
    t_single = time.perf_counter() - t0

    agree = float((np.asarray(idx) == np.asarray(ri)).mean())
    print(f"sharded top-10 == single-device top-10: {agree:.3f} agreement")
    print(f"wall: sharded {t_sharded*1e3:.1f} ms, "
          f"single {t_single*1e3:.1f} ms ({n_dev} x "
          f"{jax.devices()[0].platform})")
    print(f"per-shard rows: {ds.n // n_dev}; gathered candidates/query: "
          f"{n_dev} x 10")


if __name__ == "__main__":
    main()
