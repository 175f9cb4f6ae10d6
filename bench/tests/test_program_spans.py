"""The readers of the program's spans on made-up traces: two requests, one
of which regenerates a cluster (an ``embed.*`` span inside ``s2.regen``
that the query-embedding readers leave out), and a trace of a program with
no spans of its own, which leaves every reading out."""
import types

import pytest

from bench import run as runmod, trace

READERS = ("embed_tokenize_ms", "embed_encode_ms", "s4_prep_ms",
           "s4_prefill_ms", "first_token_ms")


def request(t, regen=False, prefill=0.1):
    """One batch of one request starting at ``t`` (seconds): the serving
    loop's query embedding, S1-S4 with two decode steps."""
    host = [(t, t + 1.0, "bench.batch"),
            (t, t + 0.010, "embed.query"),
            (t, t + 0.001, "embed.tokenize"),
            (t + 0.001, t + 0.010, "embed.encode"),
            (t + 0.010, t + 0.012, "s1.plan"),
            (t + 0.010, t + 0.012, "s1.stage"),
            (t + 0.012, t + 0.020, "s2.fetch"),
            (t + 0.020, t + 0.025, "s3.score"),
            (t + 0.025, t + 0.200, "s4.generate"),
            (t + 0.025, t + 0.200, "s4.answer"),
            (t + 0.025, t + 0.027, "s4.tokenize"),
            (t + 0.027, t + 0.028, "s4.kv_init"),
            (t + 0.028, t + 0.028 + prefill, "s4.prefill"),
            (t + 0.128, t + 0.140, "s4.decode_step"),
            (t + 0.140, t + 0.141, "s4.decode_step")]
    if regen:
        host += [(t + 0.012, t + 0.020, "s2.regen"),
                 (t + 0.013, t + 0.014, "embed.tokenize"),
                 (t + 0.014, t + 0.019, "embed.encode")]
    return host


def window(host):
    t = trace.Trace(ops=[[(0.0, 1.0, "fusion", "jit_generator_decode(1)")]],
                    host=[(0.0, 10.0, "bench.window")] + host)
    return types.SimpleNamespace(trace=t)


@pytest.mark.parametrize("name, expected", [
    ("embed_tokenize_ms", 1.0),
    ("embed_encode_ms", 9.0),
    ("s4_prep_ms", 3.0),
    ("s4_prefill_ms", 100.0),
    ("first_token_ms", 118.0),
])
def test_reader_on_made_up_spans(name, expected):
    w = window(request(1.0) + request(3.0, regen=True))
    assert runmod.load_reader(name)(w) == pytest.approx(expected)


def test_spans_outside_the_window_are_not_read():
    w = window(request(1.0) + request(12.0, prefill=0.3))
    assert runmod.load_reader("s4_prefill_ms")(w) == pytest.approx(100.0)
    assert runmod.load_reader("first_token_ms")(w) == pytest.approx(118.0)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_leaves_the_metric_out(name):
    bench_only = [iv for iv in request(1.0) if iv[2] in
                  ("bench.batch", "embed.query", "s1.plan", "s2.fetch",
                   "s3.score", "s4.generate")]
    assert runmod.load_reader(name)(window(bench_only)) is None
    assert runmod.load_reader(name)(types.SimpleNamespace(trace=None)) \
        is None
