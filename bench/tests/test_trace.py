"""The trace reduction: interval arithmetic, a made-up trace, and the
refusal of a trace with no device plane."""
import pytest

from bench import trace


def test_union_and_overlap():
    u = trace.union([(0, 1), (0.5, 2), (3, 4)])
    assert u == [(0, 2), (3, 4)]
    assert trace.overlap(u, [(1, 3.5)]) == pytest.approx(1.5)


def fake():
    ops = [[(1.0, 1.5, "fusion.1", "jit_step(1)"),
            (1.4, 2.0, "custom-call.2", "jit_slab_topk_pallas(7)"),
            (3.0, 3.2, "fusion.3", "jit_step(1)")]]
    host = [(0.0, 10.0, "bench.window"), (1.0, 4.0, "bench.batch"),
            (2.0, 3.0, "s2.fetch"), (5.0, 6.0, "bench.batch")]
    return trace.Trace(ops=ops, host=host)


def test_reduction_on_made_up_intervals():
    t = fake()
    assert trace.busy_seconds(t) == pytest.approx(1.2)
    # batches cover 4 s, the device ran 1.2 s of them
    assert trace.idle_share(t) == pytest.approx(100 * (1 - 1.2 / 4))
    assert trace.kernel_seconds(t, "slab_topk") == pytest.approx(0.6)
    b = trace.breakdown(t)
    assert b["device_ops"][0] == ["jit_step", pytest.approx(0.7)]
    labels = dict(b["idle_gaps"])
    assert labels["s2.fetch"] == pytest.approx(1.0)
    assert labels["bench.batch"] == pytest.approx(1.8)
    assert labels["waiting for a request"] == pytest.approx(6.0)


def test_breakdown_counts_a_nested_operation_once():
    t = fake()
    # a loop's body ops run inside its while op: 0.4 s of the program
    t.ops[0] += [(6.0, 6.4, "while.1", "jit_decode(2)"),
                 (6.1, 6.2, "fusion.4", "jit_decode(2)"),
                 (6.2, 6.3, "fusion.5", "jit_decode(2)")]
    b = dict(trace.breakdown(t)["device_ops"])
    assert b["jit_decode"] == pytest.approx(0.4)
    assert b["jit_step"] == pytest.approx(0.7)


def test_a_trace_without_a_device_plane_is_refused(tmp_path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no TPU plane"):
        trace.load(trace.find_xplane(str(tmp_path)))
