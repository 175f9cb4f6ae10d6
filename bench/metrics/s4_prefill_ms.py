"""Host milliseconds per request in the generator's prefill, from its
dispatch through the first token's readback (program span ``s4.prefill``),
in the traced part of the window."""
from bench import program_spans


def read(w):
    return program_spans.per_request_ms(w, "s4.prefill")
