"""Host milliseconds per request in the query embedding's encoder, from its
dispatch through the readback of its rows (program span ``embed.encode``,
outside S2's regeneration), in the traced part of the window."""
from bench import program_spans


def read(w):
    return program_spans.per_request_ms(w, "embed.encode", outside="s2.")
