"""Host milliseconds per request in the generator's decode: the decode steps
dispatched back to back and the one readback of their tokens (program span
``s4.decode``), in the traced part of the window."""
from bench import program_spans


def read(w):
    return program_spans.per_request_ms(w, "s4.decode")
