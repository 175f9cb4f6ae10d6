"""The ``s4_decode_ms`` reader on made-up traces: two requests whose decode
(``s4.decode``) holds its dispatches and the tokens' readback, and a trace
of a program with no such span, which leaves the reading out."""
import types

import pytest

from bench import run as runmod, trace


def request(t, decode=0.06):
    """One batch of one request starting at ``t`` (seconds): a 0.1 s
    prefill, then two decode dispatches and the readback in ``decode``
    seconds."""
    return [(t, t + 1.0, "bench.batch"),
            (t + 0.025, t + 0.200, "s4.generate"),
            (t + 0.028, t + 0.128, "s4.prefill"),
            (t + 0.128, t + 0.128 + decode, "s4.decode"),
            (t + 0.128, t + 0.129, "s4.decode_step"),
            (t + 0.129, t + 0.130, "s4.decode_step"),
            (t + 0.130, t + 0.128 + decode, "s4.read_tokens")]


def window(host):
    t = trace.Trace(ops=[[(0.0, 1.0, "fusion", "jit_generator_decode(1)")]],
                    host=[(0.0, 10.0, "bench.window")] + host)
    return types.SimpleNamespace(trace=t)


def test_reader_on_made_up_spans():
    w = window(request(1.0) + request(3.0, decode=0.08)
               + request(12.0, decode=0.5))      # after the window
    assert runmod.load_reader("s4_decode_ms")(w) == pytest.approx(70.0)


def test_a_program_without_the_span_leaves_the_metric_out():
    parent = [iv for iv in request(1.0)
              if iv[2] not in ("s4.decode", "s4.read_tokens")]
    assert runmod.load_reader("s4_decode_ms")(window(parent)) is None
    assert runmod.load_reader("s4_decode_ms")(
        types.SimpleNamespace(trace=None)) is None
