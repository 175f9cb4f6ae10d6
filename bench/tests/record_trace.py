"""Record the small chip trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py bench/testdata/small.xplane.pb

On a TPU: three "batches" inside a ``bench.window`` span, each a centroid
probe (``ivf_topk``), a slab scoring (``slab_topk``) and a bf16 matmul
standing in for generation, under the benchmark's span names, with host
sleeps between them so that the trace has idle gaps of known labels.
Everything is compiled before the trace starts.
"""
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def main(out: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import cell as cellmod, trace
    from repro.kernels.ivf_topk.ops import topk_ip
    from repro.kernels.slab_topk.ops import slab_topk

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace.py: needs a TPU")
    rng = np.random.default_rng(0)
    cents = rng.standard_normal((781, 768)).astype(np.float32)
    queries = rng.standard_normal((8, 768)).astype(np.float32)
    slab = rng.standard_normal((2000, 768)).astype(np.float32)
    virt = np.tile(np.arange(2000, dtype=np.int32), (8, 1))
    w = jnp.asarray(rng.standard_normal((2048, 2048)), jnp.bfloat16)
    gen = jax.jit(lambda w: (w @ w).sum())

    def batch(spans):
        with spans.span("bench.batch"):
            with spans.span("s1.plan"):
                np.asarray(topk_ip(cents, queries, 8)[1])
            with spans.span("s3.search_finish"):
                np.asarray(slab_topk(slab, queries, virt, 10)[1])
                time.sleep(0.01)
            with spans.span("s4.generate"):
                gen(w).block_until_ready()
                time.sleep(0.005)

    spans = cellmod.Spans()
    batch(spans)                                   # compile everything
    tmp = ROOT / "bench" / ".trace"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            batch(spans)
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = trace.find_xplane(str(tmp))
    shutil.copy(src, out)
    t = trace.load(out)
    print(f"recorded {Path(out).stat().st_size} bytes; busy "
          f"{trace.busy_seconds(t)} s of {t.window[1] - t.window[0]} s; "
          f"breakdown {trace.breakdown(t)}")


if __name__ == "__main__":
    main(sys.argv[1])
